"""Smoke-size configurations and mixes for the CPU tests: the program's
own smoke presets, under the same keys as the cell files."""

GRANITE = {
    "name": "granite-smoke", "family": "decoder_lm",
    "arch": "granite-moe-1b-a400m", "preset": "smoke",
    "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
    "program": {"pad_vocab_to": 256, "moe_capacity_factor": 1.25,
                "moe_group_size": 512},
}

QWEN = {
    "name": "qwen-smoke", "family": "decoder_lm",
    "arch": "qwen3-8b", "preset": "smoke",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "qk_norm": True,
    "program": {"pad_vocab_to": 256},
}

TRAIN = {
    "kind": "train", "batch": 2, "seq": 64, "zipf_a": 1.2, "copy_period": 16,
    "sparsity": {"n": 2, "m": 8, "method": "bdwp", "lam": 2e-4},
    "path": {"pregen": True, "pregen_pack": False, "use_pallas": False},
    "optimizer": {"lr": 0.01, "momentum": 0.9, "weight_decay": 5e-4,
                  "warmup_steps": 0, "min_lr_frac": 1.0},
    "checked_steps": 3,
}
