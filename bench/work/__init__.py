"""Required work of each kernel, from the configuration's arithmetic."""
