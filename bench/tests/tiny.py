"""A tiny dense decoder configuration and cells for CPU tests."""

import copy

TINY = {
    "name": "tiny", "model_type": "qwen2", "hidden_act": "silu",
    "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "num_hidden_layers": 2, "vocab_size": 2048,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "attention_bias": True,
    "torch_dtype": "bfloat16",
}

CHAT = {"prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                   "min": 4, "max": 64},
        "output": {"dist": "lognormal", "median": 12, "sigma": 0.7,
                   "min": 4, "max": 24}}

DOCS = {"prefix": {"documents": 3, "zipf": 1.0,
                   "length": {"dist": "uniform", "min": 33, "max": 70}},
        "prompt": {"dist": "choice", "values": [4, 8]},
        "output": {"dist": "uniform", "min": 4, "max": 12}}

CONTIGUOUS = {"serve": {"cache": "contiguous", "slots": 4, "max_len": 128},
              "requests_per_call": 8, "check_tokens": 10000,
              "limits": {"max_logit_gap": 0.05, "mean_logit_gap": 1.5e-4}}

PAGED = {"serve": {"cache": "paged", "slots": 4, "max_len": 128,
                   "page_size": 16},
         "requests_per_call": 8, "check_tokens": 10000,
         "limits": {"max_logit_gap": 0.05, "mean_logit_gap": 1.5e-4}}


def config(**changes):
    cfg = copy.deepcopy(TINY)
    cfg.update(changes)
    return cfg
