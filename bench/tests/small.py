"""A configuration and mixes small enough for CPU tests: the cells' code
paths at widths one 128-block can prune (two blocks per side)."""

SMALL_MODEL = {
    "name": "small", "arch": "qwen1.5-4b", "reference": "dense_decoder",
    "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 128,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "qkv_bias": True, "bcr_block": 128, "bcr_keep": 0.25,
    "build_chunk_layers": 1,
    "engine": {"n_slots": 4, "capacity": 128, "page_size": 16,
               "kv_pages": None, "max_admit_per_step": 2},
}

SMALL_CLOSED = {
    "loop": "closed", "clients": 5, "pool": 64, "strata": 4,
    "warm_in_s": 0.5,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 40},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 3,
               "max": 10},
}

SMALL_OPEN = dict(SMALL_CLOSED, loop="open", rate=4.0)

#: longer answers, so a run's sample holds some hundreds of served tokens
#: and the precision control's gaps show above the sound program's
SMALL_ANSWERS = dict(SMALL_CLOSED, clients=6, output={
    "dist": "lognormal", "median": 24, "sigma": 0.5, "min": 12, "max": 40})
