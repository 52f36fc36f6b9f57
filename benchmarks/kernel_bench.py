"""Kernel micro-bench: Pallas (interpret) vs jnp oracle wall time on CPU,
plus the analytic TPU-v5e roofline estimate for the production tile.
Includes the paged-attention cases the serving engine hot path runs:
paged decode across page sizes and paged (gathered) chunked prefill."""
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv, timed
from repro.kernels.ops import (
    chunked_prefill_attention_op, chunked_prefill_attention_ref,
    gather_pages, paged_decode_attention_op, paged_decode_attention_ref,
    paged_prefill_attention_op,
)


def _pool(rng, n_pages, page, KV, hd):
    """Random pages in the pool's head-major layout (n_pages, KV, page, hd)."""
    return jnp.asarray(rng.standard_normal((n_pages, KV, page, hd)),
                       jnp.float32)


def main(csv: Csv | None = None):
    csv = csv or Csv()
    rng = np.random.default_rng(0)
    B, Tq, S, H, KV, hd = 1, 64, 256, 8, 2, 128
    q = jnp.asarray(rng.standard_normal((B, Tq, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    off = jnp.zeros((B,), jnp.int32)
    qh, kh, vh = (x.swapaxes(1, 2) for x in (q, k, v))   # head-major op
    _, us = timed(lambda: chunked_prefill_attention_op(
        qh, kh, vh, off, bq=32, bk=64, interpret=True).block_until_ready())
    _, us_ref = timed(lambda: chunked_prefill_attention_ref(
        q, k, v, off).block_until_ready())
    flops = 4 * B * Tq * S * H * hd
    v5e = flops / 197e12 * 1e6
    csv.add("kernel/chunked_prefill", us,
            f"ref_us={us_ref:.0f} tpu_v5e_roofline_us={v5e:.2f}")

    n_pages, page, ppseq = 64, 16, 16
    q2 = jnp.asarray(rng.standard_normal((4, H, hd)), jnp.float32)
    kp = _pool(rng, n_pages, page, KV, hd)
    vp = _pool(rng, n_pages, page, KV, hd)
    tbl = jnp.asarray(rng.integers(0, n_pages, (4, ppseq)), jnp.int32)
    lens = jnp.full((4,), page * ppseq, jnp.int32)
    _, us = timed(lambda: paged_decode_attention_op(
        q2, kp, vp, tbl, lens, interpret=True).block_until_ready())
    _, us_ref = timed(lambda: paged_decode_attention_ref(
        q2, kp, vp, tbl, lens).block_until_ready())
    bytes_moved = 2 * 4 * ppseq * page * KV * hd * 4
    v5e = bytes_moved / 819e9 * 1e6
    csv.add("kernel/paged_decode", us,
            f"ref_us={us_ref:.0f} tpu_v5e_hbm_roofline_us={v5e:.2f}")

    # paged decode across page sizes (the pool-layout tuning knob: small
    # pages pack ragged requests densely, large pages amortize gathers)
    for psize in (8, 16, 32):
        pps = 256 // psize
        nps = 4 * pps + 4          # room for 4 sequences' disjoint tables
        kp2 = _pool(rng, nps, psize, KV, hd)
        vp2 = _pool(rng, nps, psize, KV, hd)
        tbl2 = jnp.asarray(
            rng.permutation(nps)[:4 * pps].reshape(4, pps), jnp.int32)
        lens2 = jnp.full((4,), 256, jnp.int32)
        _, us = timed(lambda: paged_decode_attention_op(
            q2, kp2, vp2, tbl2, lens2, interpret=True).block_until_ready())
        bytes_moved = 2 * 4 * 256 * KV * hd * 4
        v5e = bytes_moved / 819e9 * 1e6
        csv.add(f"kernel/paged_decode_p{psize}", us,
                f"pages_per_seq={pps} tpu_v5e_hbm_roofline_us={v5e:.2f}")

    # paged chunked prefill: micro-request beta resuming mid-prompt
    # against a block-table pool (gather + chunked kernel)
    psize, pps = 16, 16
    nps = 4 * pps + 2
    Tq2, ctx = 64, 128
    qp3 = jnp.asarray(rng.standard_normal((4, Tq2, H, hd)), jnp.float32)
    kp3 = _pool(rng, nps, psize, KV, hd)
    vp3 = _pool(rng, nps, psize, KV, hd)
    tbl3 = jnp.asarray(rng.integers(0, nps, (4, pps)), jnp.int32)
    off3 = jnp.full((4,), ctx, jnp.int32)
    _, us = timed(lambda: paged_prefill_attention_op(
        qp3, kp3, vp3, tbl3, off3, bq=32, bk=64,
        interpret=True).block_until_ready())
    _, us_ref = timed(lambda: chunked_prefill_attention_ref(
        qp3, gather_pages(kp3, tbl3).swapaxes(1, 2),
        gather_pages(vp3, tbl3).swapaxes(1, 2), off3).block_until_ready())
    flops = 4 * 4 * Tq2 * (ctx + Tq2) * H * hd
    v5e = flops / 197e12 * 1e6
    csv.add("kernel/paged_prefill", us,
            f"ref_us={us_ref:.0f} tpu_v5e_roofline_us={v5e:.2f}")
    return csv


if __name__ == "__main__":
    main()
