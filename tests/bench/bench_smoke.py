"""A tiny configuration and mix for the benchmark's CPU tests: the
harness's own code paths at a size the Pallas interpreter runs quickly."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.families import dense_gqa  # noqa: E402

SPEC = dense_gqa.spec({
    "family": "dense_gqa", "name": "smoke", "hidden_size": 256,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 32,
    "intermediate_size": 512, "vocab_size": 512, "num_hidden_layers": 2,
    "qkv_bias": True, "tie_word_embeddings": False, "rope_theta": 1e6,
    "rms_norm_eps": 1e-6,
    "deployment": {"n_slots": 4, "max_context": 32, "kv_pool_tokens": 128,
                   "instances": 1, "chips": 1}})
MIX = {"classes": [{"weight": 1.0, "prompt": {"median": 10, "sigma": 0.3},
                    "output": {"median": 6, "sigma": 0.3}}],
       "prompt_clip": [4, 16], "output_clip": [3, 8], "rate_per_s": 4.0,
       "preload_s": 1.0, "tbt_limit_ms": 100, "ttft_limit_ms": 1000}
# bf16 served tokens read gaps of about 5e-3 against the float32
# reference at this size; the fp8 control about 0.1, each fault 0.8-2.
LIMIT = 0.03
SEED = 12345678901          # above 32 bits, as the driver's seeds are
