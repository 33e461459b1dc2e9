"""A cell small enough for the CPU: the Qwen2 family at toy widths, with
the chat cell's metric lists from BENCHMARK.json."""
import copy

from bench.harness import spec

CONFIG = {"model_type": "qwen2", "name": "tiny", "hidden_size": 64,
          "intermediate_size": 128, "num_attention_heads": 4,
          "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
          "tie_word_embeddings": True, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16",
          "max_position_embeddings": 512}
OPEN = {"loop": "open", "prompt_len": 8,
        "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "rate_rps": 20.0, "burst": {"every_s": 1, "len_s": 0.25, "x": 3},
        "arrival_seed": 0}
CLOSED = {"loop": "closed", "clients": 4, "prompt_len": 8,
          "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
SHAPE = {"slots": 4, "cache_len": 32, "block_k": 4, "pipeline_depth": 2,
         "check": {"tokens": 40, "requests": 4},
         "limits": {"kv_err": 0.02, "logit_err": 0.03, "off_share": 0.02}}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def cell(closed: bool = False, untied: bool = False) -> spec.Cell:
    c = spec.cell("qwen2.5-3b.batch" if closed else "qwen2.5-3b.chat")
    c.config = dict(CONFIG, tie_word_embeddings=not untied)
    c.traffic = copy.deepcopy(CLOSED if closed else OPEN)
    c.shape = copy.deepcopy(SHAPE)
    return c
