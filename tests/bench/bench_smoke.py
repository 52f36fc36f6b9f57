"""A tiny configuration and mix for the benchmark's CPU tests: the
harness's own code paths at a size the Pallas interpreter runs quickly."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.model import ModelSpec  # noqa: E402

SPEC = ModelSpec(name="smoke", d_model=256, n_heads=8, n_kv_heads=2,
                 head_dim=32, d_ff=512, vocab=512, n_layers=2,
                 qkv_bias=True, tied=False, rope_theta=1e6,
                 rope_fraction=1.0, norm_eps=1e-6, n_slots=4,
                 max_context=32, kv_pool_tokens=128)
MIX = {"classes": [{"weight": 1.0, "prompt": {"median": 10, "sigma": 0.3},
                    "output": {"median": 6, "sigma": 0.3}}],
       "prompt_clip": [4, 16], "output_clip": [3, 8], "rate_per_s": 4.0,
       "preload_s": 1.0, "tbt_limit_ms": 100, "ttft_limit_ms": 1000}
# bf16 served tokens read gaps of about 5e-3 against the float32
# reference at this size; the fp8 control about 0.1, each fault 0.8-2.
LIMIT = 0.03
SEED = 12345678901          # above 32 bits, as the driver's seeds are
