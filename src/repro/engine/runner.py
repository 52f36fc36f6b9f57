"""Per-instance execution engine.

One ``InstanceEngine`` is the runtime of one *unified GPU instance* in
DynaServe terms: it owns a slot-pooled KV/state cache and executes the
batches the local scheduler composes.  A batch is a set of (slot, token
span) items — prefill chunks of any length and decode steps (length 1)
run together in ONE padded forward call, which is exactly the paper's
unified mixed batch.

The engine deliberately runs real JAX compute so the end-to-end serving
tests exercise the same code path the TPU deployment lowers; the cluster
*simulator* (repro.sim) reuses only the cost model, not this engine.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import get_precision
from repro.engine.block_allocator import (
    BlockAllocator, CapacityError, OutOfPages, pages_for,
)
from repro.engine.prefix_cache import PrefixCache
from repro.kernels.ops import from_pool_layout, to_pool_layout
from repro.models.config import ModelConfig
from repro.models.model import (
    forward, init_cache, init_paged_cache, supports_paged_kv,
)
from repro.models.tp import tp_context
from repro.utils import spans
from repro.utils.sharding import tp_cache_specs, tp_param_specs

DEFAULT_MAX_CHUNK = 512


def bucket_ladder(max_chunk: int) -> Tuple[int, ...]:
    """Power-of-two padding buckets up to (at least) ``max_chunk`` — the
    ladder is derived from the engine's configured max chunk instead of
    a hardcoded tuple, so engines serving longer chunks just get more
    rungs."""
    out, b = [], 1
    while b < max_chunk:
        out.append(b)
        b <<= 1
    out.append(b)
    return tuple(out)


BUCKETS = bucket_ladder(DEFAULT_MAX_CHUNK)   # default ladder (compat)


def bucket_of(n: int, buckets: Sequence[int] = BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(
        f"chunk of {n} tokens exceeds max bucket {buckets[-1]}; "
        f"construct the engine with max_chunk >= {n}")


@dataclasses.dataclass
class BatchItem:
    slot: int
    tokens: np.ndarray          # (t,) int32 token ids to feed
    pos_offset: int             # absolute position of tokens[0]
    want_logits: bool = False   # final chunk of prefill / decode step


@dataclasses.dataclass(eq=False)
class StepHandle:
    """An in-flight forward step: the jitted call has been issued (jax
    dispatches asynchronously) but its logits have not been fetched to
    host.  ``ready()`` probes completion without blocking;
    ``InstanceEngine.collect_batch`` blocks and materializes the
    results."""
    items: Sequence[BatchItem]
    logits: object              # device array, possibly still computing
    seq: int = -1               # the engine's step number (its spans')

    def ready(self) -> bool:
        return self.logits.is_ready()


def _nbytes(tree) -> int:
    """Array bytes in a handoff piece (or a list of them)."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree)
               if isinstance(x, np.ndarray))


class InstanceEngine:
    """One unified instance.

    ``kv_mode`` selects the cache substrate:

    * ``"paged"`` — block-table page pool (``init_paged_cache`` +
      ``BlockAllocator``); attention runs through the Pallas paged-decode
      / chunked-prefill kernels (interpret mode on CPU).  Requests grow
      by appending pages, so a sequence is bounded by the *pool*, not a
      per-slot ``max_len``.
    * ``"dense"`` — the legacy (n_slots, max_len) slot cache; required
      for ring-buffer / recurrent / enc-dec architectures.
    * ``"auto"`` (default) — paged when the architecture supports it.

    ``devices`` places the instance: one device pins its params and KV
    pool there; a list of n > 1 devices makes it *sharded*, a
    1-D ``("model",)`` sub-mesh and every step runs as one jitted
    ``shard_map`` over it — tensor-parallel attention/MLP (heads / ffn
    sharded, psum at the output projections) and expert-parallel MoE
    (each shard owns a contiguous expert slice).  KV pages shard over
    kv_heads; ``export_state`` gathers to the portable single-device
    piece format so handoffs cross shard widths transparently.
    """

    def __init__(self, cfg: ModelConfig, params, n_slots: int = 8,
                 max_len: int = 512, window_override: Optional[int] = None,
                 kv_mode: str = "auto", page_size: int = 8,
                 n_pages: Optional[int] = None,
                 max_chunk: int = DEFAULT_MAX_CHUNK,
                 prefix_cache: bool = False,
                 kv_precision: str = "bf16",
                 devices: Optional[Sequence] = None):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.window_override = window_override
        self.max_chunk = max_chunk
        self.buckets = bucket_ladder(max_chunk)
        self.kv_precision = get_precision(kv_precision)
        self.devices = list(devices) if devices else None
        self.tp = len(self.devices) if self.devices else 1
        if self.tp > 1:
            self._validate_tp()
        if kv_mode not in ("auto", "paged", "dense"):
            raise ValueError(f"unknown kv_mode {kv_mode!r}")
        if kv_mode == "paged" and not supports_paged_kv(cfg):
            raise ValueError(f"{cfg.name} cannot run a paged KV cache")
        if kv_mode == "paged" and window_override is not None:
            raise ValueError("paged KV has no sliding-window support; "
                             "window_override requires kv_mode='dense'")
        self.paged = (kv_mode == "paged" or
                      (kv_mode == "auto" and supports_paged_kv(cfg)
                       and window_override is None))
        if self.kv_precision.quantized and not self.paged:
            raise ValueError("quantized KV formats live on the page pool; "
                             f"kv_precision={self.kv_precision.name!r} "
                             f"requires a paged KV mode")
        # a one-device instance allocates its pool on its own device
        device = self.devices[0] if self.tp == 1 and self.devices else None
        if self.paged:
            self.page_size = page_size
            self.n_pages = (n_pages if n_pages is not None
                            else n_slots * pages_for(max_len, page_size))
            with jax.default_device(device):
                self.cache = init_paged_cache(
                    cfg, self.n_pages, page_size,
                    kv_precision=self.kv_precision)
            self.allocator = BlockAllocator(self.n_pages, page_size, n_slots,
                                            precision=self.kv_precision)
            self.page_buckets = bucket_ladder(self.n_pages)
        else:
            if prefix_cache:
                raise ValueError("the shared-prefix cache lives on the "
                                 "page pool; it requires a paged KV mode")
            self.page_size = None
            self.n_pages = None
            self.allocator = None
            with jax.default_device(device):
                self.cache = init_cache(cfg, n_slots, max_len,
                                        window_override=window_override)
        # shared-prefix KV cache: trie over the page pool + per-slot
        # claims; the allocator evicts through it under pressure
        self.prefix: Optional[PrefixCache] = None
        self._claims: Dict[int, object] = {}
        if prefix_cache:
            self.prefix = PrefixCache(self.page_size)
            self.allocator.evictor = self._evict_cached_page
        # sharded instance: place params and the KV pool on the sub-mesh
        self.mesh = None
        self._param_specs = None
        self._cache_specs = None
        if self.tp > 1:
            self._shard_instance()
        elif device is not None:
            self.params = jax.device_put(params, device)
            self.cache = jax.device_put(self.cache, device)
        self.free_slots = list(range(n_slots))
        self.slot_owner: Dict[int, str] = {}
        self._step_fns: Dict[tuple, callable] = {}
        # steps run; the next step's number in its profiler spans
        self.iterations = 0
        self.prefix_hit_tokens = 0

    # ---------------- tensor/expert parallelism ----------------
    def _validate_tp(self) -> None:
        """A sharded instance requires every shardable dim to divide the
        mesh: a q-sharded / kv-replicated GQA split would break the
        contiguous-group attention reshape, and partially-sharded MLPs
        buy nothing.  Archs with recurrent / cross / frontend state keep
        per-slot host scatter paths that are not shard-aware."""
        cfg, tp = self.cfg, self.tp
        bad: List[str] = []
        if not all(k in ("attn", "local_attn") for k in cfg.layer_pattern):
            bad.append(f"layer pattern {cfg.layer_pattern!r} "
                       f"(attention-only archs shard)")
        if cfg.tail_kinds or cfg.cross_attention or \
                cfg.arch_type in ("vlm", "audio"):
            bad.append("tail/cross/frontend blocks do not shard")
        if cfg.n_heads % tp:
            bad.append(f"n_heads={cfg.n_heads} % {tp} != 0")
        if cfg.n_kv_heads % tp:
            bad.append(f"n_kv_heads={cfg.n_kv_heads} % {tp} != 0")
        if cfg.moe_experts:
            if cfg.moe_experts % tp:
                bad.append(f"moe_experts={cfg.moe_experts} % {tp} != 0")
        elif cfg.mlp != "none" and cfg.d_ff % tp:
            bad.append(f"d_ff={cfg.d_ff} % {tp} != 0")
        if self.kv_precision.quantized:
            bad.append(f"kv_precision={self.kv_precision.name!r} "
                       f"(quantized scale planes have no head dim to "
                       f"shard)")
        if bad:
            raise ValueError(
                f"{cfg.name} cannot run as a {tp}-device sharded "
                f"instance: " + "; ".join(bad))

    def _shard_instance(self) -> None:
        """Build the ("model",) sub-mesh and place params + cache with
        Megatron-style NamedShardings; the jitted shard_map steps then
        consume them without resharding."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        self.mesh = Mesh(np.asarray(self.devices), ("model",))

        def put(tree, specs):
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), specs,
                is_leaf=lambda s: isinstance(s, P))
            return jax.device_put(tree, shardings)

        self._param_specs = tp_param_specs(self.cfg, self.params)
        self._cache_specs = tp_cache_specs(self.cache)
        self.params = put(self.params, self._param_specs)
        self.cache = put(self.cache, self._cache_specs)

    # ---------------- slot management ----------------
    def alloc(self, req_id: str) -> int:
        if not self.free_slots:
            raise CapacityError(
                f"no free KV slot for {req_id}: all {self.n_slots} in use")
        slot = self.free_slots.pop(0)
        self.slot_owner[slot] = req_id
        return slot

    def free(self, slot: int) -> None:
        self.slot_owner.pop(slot, None)
        if self.allocator is not None:
            self._drop_claim(slot)
            self.allocator.free_slot(slot)
        self.free_slots.append(slot)

    def preempt(self, slot: int) -> None:
        """Release the slot's KV pages but keep the slot: the scheduler
        re-queues the request for recompute under memory pressure."""
        if self.allocator is not None:
            self._drop_claim(slot)
            self.allocator.trim(slot)

    @property
    def n_free(self) -> int:
        return len(self.free_slots)

    @property
    def free_pages(self) -> Optional[int]:
        """Free pages *including* what the prefix cache would give back
        under pressure (unpinned cached prefixes are evicted before any
        request is preempted, so the schedulers may budget against
        them)."""
        if self.allocator is None:
            return None
        extra = self.prefix.evictable_pages if self.prefix else 0
        return self.allocator.free_pages + extra

    @property
    def mem_pressure(self) -> float:
        if self.allocator is None:
            return 0.0
        return 1.0 - self.free_pages / self.n_pages

    # ---------------- shared-prefix cache ----------------
    def _evict_cached_page(self) -> Optional[int]:
        return self.prefix.evict_one() if self.prefix else None

    def _drop_claim(self, slot: int) -> None:
        claim = self._claims.pop(slot, None)
        if claim is not None:
            self.prefix.release(claim)

    def register(self, slot: int, tokens,
                 max_tokens: Optional[int] = None) -> int:
        """Match the longest cached prefix of ``tokens`` (page-aligned,
        capped to ``max_tokens``) and splice its pages into the slot's
        block table, pinning them for the slot's lifetime.  Returns the
        number of prefix tokens whose prefill is thereby skipped (0 on
        a miss or with the cache disabled)."""
        if self.prefix is None or self.allocator.len_of(slot) > 0:
            return 0
        claim = self.prefix.claim(tokens, max_tokens=max_tokens,
                                  precision=self.kv_precision.name)
        if not claim.nodes:
            return 0
        self.allocator.splice(slot, claim.pages, claim.tokens)
        self._claims[slot] = claim
        self.prefix_hit_tokens += claim.tokens
        return claim.tokens

    def lookup_prefix(self, tokens) -> int:
        """Non-mutating probe: cached prefix length in tokens (the
        global scheduler scores placements with it)."""
        if self.prefix is None:
            return 0
        return self.prefix.match_len(tokens,
                                     precision=self.kv_precision.name)

    def remember(self, slot: int, tokens) -> int:
        """Index the slot's resident full pages under their token ids so
        later requests sharing the prefix can splice them (called as the
        slot's request leaves the engine, *before* ``free``).  Newly
        adopted pages gain a cache reference and survive the slot;
        chunks already cached keep their existing page (the slot's
        duplicate is freed normally).  Returns pages adopted."""
        if self.prefix is None:
            return 0
        page = self.page_size
        n = (min(len(tokens), self.allocator.len_of(slot)) // page) * page
        if n <= 0:
            return 0
        adopted = self.prefix.insert(tokens[:n],
                                     self.allocator.pages_of(slot),
                                     precision=self.kv_precision.name)
        self.allocator.retain(adopted)
        return len(adopted)

    def check_invariants(self) -> None:
        """Refcount coherence (debug): allocator refs == table refs +
        prefix-cache refs for every page."""
        if self.allocator is not None:
            refs = self.prefix.page_refcounts() if self.prefix else {}
            self.allocator.check(cache_refs=refs)

    # ---------------- jitted unified step ----------------
    def _step_fn(self, T: int, n_pp: int = 0):
        key = (T, n_pp)
        if key in self._step_fns:
            return self._step_fns[key]
        cfg, wo, page = self.cfg, self.window_override, self.page_size

        if n_pp:
            def step_body(params, cache, tokens, pos_offset, n_valid,
                          active, tables):
                logits, new_cache, _ = forward(
                    params, cfg, tokens, cache=cache, pos_offset=pos_offset,
                    active=active, n_valid=n_valid, last_only=True,
                    block_tables=tables, page_size=page)
                return logits[:, 0], new_cache
        else:
            def step_body(params, cache, tokens, pos_offset, n_valid,
                          active):
                logits, new_cache, _ = forward(
                    params, cfg, tokens, cache=cache, pos_offset=pos_offset,
                    active=active, n_valid=n_valid, last_only=True,
                    window_override=wo)
                return logits[:, 0], new_cache

        if self.tp > 1:
            step = jax.jit(self._shard_step(step_body, n_batch_args=5 if n_pp else 4))
        else:
            step = jax.jit(step_body)
        self._step_fns[key] = step
        return step

    def _shard_step(self, step_body, n_batch_args: int):
        """Wrap a step body in ``shard_map`` over the instance sub-mesh.
        Params/cache enter per their Megatron specs; batch operands and
        logits are replicated.  ``tp_context`` marks the trace so the
        model's output projections psum over the axis."""
        from jax.sharding import PartitionSpec as P

        def body(params, cache, *batch):
            with tp_context("model"):
                return step_body(params, cache, *batch)

        in_specs = (self._param_specs, self._cache_specs) + \
            (P(),) * n_batch_args
        return jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                             out_specs=(P(), self._cache_specs),
                             check_vma=False)

    # ---------------- execution ----------------
    def run_batch(self, items: Sequence[BatchItem]) -> Dict[int, np.ndarray]:
        """Execute one unified mixed batch; returns {slot: last-token logits}
        for items with want_logits."""
        return self.collect_batch(self.dispatch_batch(items))

    def dispatch_batch(self, items: Sequence[BatchItem]) \
            -> Optional[StepHandle]:
        """Issue one unified mixed batch without waiting for the device.

        All host-side work happens here — padding, block-table growth,
        the jitted call — and jax's async dispatch returns the logits as
        a device array immediately.  The caller overlaps host work
        (scheduling the next batch, pumping KV streams) with the device
        and later blocks in ``collect_batch``.  Returns ``None`` for an
        empty batch."""
        if not items:
            return None
        with spans.span("engine.dispatch") as sp:
            T = bucket_of(max(len(it.tokens) for it in items), self.buckets)
            B = self.n_slots
            tokens = np.zeros((B, T), np.int32)
            pos_off = np.zeros((B,), np.int32)
            n_valid = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            for it in items:
                t = len(it.tokens)
                tokens[it.slot, :t] = it.tokens
                pos_off[it.slot] = it.pos_offset
                n_valid[it.slot] = t
                active[it.slot] = True
            args = ()
            n_pp = 0
            if self.paged:
                # grow block tables to cover every item's span before the
                # write; OutOfPages here means the scheduler overcommitted.
                # Growing may copy-on-write-fork shared prefix pages the
                # write region touches — apply the KV copies first.
                with spans.span("engine.tables") as tsp:
                    forks: List[Tuple[int, int]] = []
                    for it in items:
                        forks.extend(self.allocator.ensure(
                            it.slot, it.pos_offset + len(it.tokens)))
                    if forks:
                        self._apply_forks(forks)
                    n_pp = bucket_of(max(1, self.allocator.max_table_len),
                                     self.page_buckets)
                    args = (jnp.asarray(self.allocator.table_array(n_pp)),)
                    if tsp is not None and T == 1:
                        # the decode kernel streams each row's own pages
                        # of the n_pp its table holds
                        tsp.set_metadata(n_pp=n_pp, pages=sum(
                            pages_for(self.allocator.len_of(it.slot),
                                      self.page_size) for it in items))
            seq = self.iterations
            with spans.span("engine.launch") as lsp:
                if lsp is not None:
                    lsp.set_metadata(
                        new_program=int((T, n_pp) not in self._step_fns))
                step = self._step_fn(T, n_pp)
                logits, self.cache = step(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(pos_off), jnp.asarray(n_valid),
                    jnp.asarray(active), *args)
            self.iterations += 1
            if sp is not None:
                sp.set_metadata(seq=seq, T=T, slots=B,
                                tokens=int(n_valid.sum()))
        return StepHandle(items=items, logits=logits, seq=seq)

    def collect_batch(self, handle: Optional[StepHandle]) \
            -> Dict[int, np.ndarray]:
        """Block on an in-flight step and return {slot: last-token
        logits} for its want_logits items."""
        if handle is None:
            return {}
        with spans.span("engine.collect") as sp:
            if sp is not None:
                # traced only: split the wait for the step from the copy
                sp.set_metadata(seq=handle.seq)
                with spans.span("engine.wait"):
                    handle.logits.block_until_ready()
            with spans.span("engine.fetch"):
                logits = np.asarray(handle.logits)
        return {it.slot: logits[it.slot]
                for it in handle.items if it.want_logits}

    def _apply_forks(self, forks: Sequence[Tuple[int, int]]) -> None:
        """Copy KV contents of copy-on-write-forked pages (old -> new)
        in one scatter per layer so the forking slot may write its
        private copy without touching the shared original."""
        old_ids = jnp.asarray([o for o, _ in forks], jnp.int32)
        new_ids = jnp.asarray([n for _, n in forks], jnp.int32)
        blocks = list(self.cache["blocks"])
        for i in range(len(blocks)):
            blocks[i] = {
                key: blocks[i][key].at[:, new_ids].set(
                    blocks[i][key][:, old_ids])
                for key in blocks[i]        # k/v pages + dequant scales
            }
        self.cache = dict(self.cache, blocks=tuple(blocks))

    def run_frontend(self, slot: int, *, extra_embeds=None, frames=None,
                     tokens: Optional[np.ndarray] = None, pos_offset: int = 0):
        """Stub-frontend prefill for VLM/audio requests: embeds the patch /
        frame embeddings (plus any leading text tokens) into the cache for
        one slot.  Runs as a dedicated call because embeddings enter below
        the token embedding layer."""
        if self.paged:
            raise ValueError("stub-frontend prefill requires a dense "
                             "cache (paged engines serve text-only "
                             "architectures)")
        B = self.n_slots
        cfg = self.cfg
        n_extra = (extra_embeds.shape[0] if extra_embeds is not None else 0)
        tok = np.zeros((B, max(1, 0 if tokens is None else len(tokens))), np.int32)
        if tokens is not None and len(tokens):
            tok[slot, :len(tokens)] = tokens
            tvalid = len(tokens)
        else:
            tok = None
            tvalid = 0
        kw = {}
        if extra_embeds is not None:
            ee = np.zeros((B,) + extra_embeds.shape, np.float32)
            ee[slot] = extra_embeds
            kw["extra_embeds"] = jnp.asarray(ee)
        if frames is not None:
            fr = np.zeros((B,) + frames.shape, np.float32)
            fr[slot] = frames
            kw["frames"] = jnp.asarray(fr)
        active = np.zeros((B,), bool)
        active[slot] = True
        total = n_extra + tvalid
        n_valid = np.full((B,), total, np.int32)
        logits, self.cache, _ = forward(
            self.params, cfg, None if tok is None else jnp.asarray(tok),
            cache=self.cache, pos_offset=jnp.full((B,), pos_offset, jnp.int32),
            active=jnp.asarray(active), n_valid=jnp.asarray(n_valid),
            last_only=True, window_override=self.window_override, **kw)
        self.iterations += 1
        return np.asarray(logits[slot, 0])

    # ---------------- micro-request state handoff ----------------
    def export_state(self, slot: int, upto: int, chunk: int = 0,
                     start: int = 0) -> List[dict]:
        """Extract the KV/state needed to resume this request elsewhere.

        Attention KV for positions [start, upto) is split into
        ``chunk``-sized pieces (chunk-based KV transfer, §4.3);
        recurrent state is O(1) and ships as a single piece.  Paged
        engines ship whole pages, so the chunk boundaries of the
        transfer align with page boundaries.  A non-zero ``start``
        (page-aligned) skips the leading prefix the destination already
        holds — the prefix-cache-aware handoff ships only the pages the
        destination's cache missed.
        """
        if self.paged:
            return self._export_paged(slot, upto, chunk, start=start)
        if start:
            raise ValueError("prefix-skipping export requires a paged "
                             "cache")
        with spans.span("engine.export") as sp:
            pieces = self._export_dense(slot, upto, chunk)
            if sp is not None:
                sp.set_metadata(rid=self.slot_owner.get(slot, ""),
                                bytes=_nbytes(pieces))
        return pieces

    def _export_dense(self, slot: int, upto: int, chunk: int) -> List[dict]:
        cfg = self.cfg
        pieces: List[dict] = []
        spans = ([(0, upto)] if not chunk else
                 [(s, min(s + chunk, upto)) for s in range(0, upto, chunk)])
        for lo, hi in spans:
            piece = {"span": (lo, hi), "blocks": []}
            for i, kind in enumerate(cfg.layer_pattern):
                c = self.cache["blocks"][i]
                if "k" in c and c["k"].shape[2] >= upto:
                    piece["blocks"].append({
                        "k": np.asarray(c["k"][:, slot, lo:hi]),
                        "v": np.asarray(c["v"][:, slot, lo:hi]),
                        "pos": np.asarray(c["pos"][:, slot, lo:hi]),
                    })
                else:
                    # ring buffer (sliding window): bounded — ship whole
                    # buffer with the final piece instead of spans
                    piece["blocks"].append(None)
            pieces.append(piece)
        final = pieces[-1]
        final["rings"] = []
        for i, kind in enumerate(cfg.layer_pattern):
            c = self.cache["blocks"][i]
            if "k" in c and c["k"].shape[2] < upto:
                final["rings"].append(
                    {k: np.asarray(v[:, slot]) for k, v in c.items()})
            else:
                final["rings"].append(None)
        # recurrent / tail / cross state rides with the final piece
        final["recurrent"] = []
        for i, kind in enumerate(cfg.layer_pattern):
            c = self.cache["blocks"][i]
            if "k" not in c:
                final["recurrent"].append(
                    {k: np.asarray(v[:, slot]) for k, v in c.items()})
            else:
                final["recurrent"].append(None)
        if "tail" in self.cache:
            final["tail"] = [
                {k: np.asarray(v[slot]) for k, v in tc.items()}
                for tc in self.cache["tail"]]
        if "cross" in self.cache:
            final["cross"] = {k: np.asarray(v[:, slot])
                              for k, v in self.cache["cross"].items()}
        return pieces

    def export_state_iter(self, slot: int, upto: int, chunk: int = 0,
                          start: int = 0):
        """Lazy chunk-at-a-time export for background KV streams: each
        ``next()`` materializes (device→host copies) exactly one piece,
        so the caller can interleave decode batches between pieces
        instead of snapshotting the whole span up front.  Paged engines
        stream pages lazily; dense caches fall back to the eager export
        (their final piece carries recurrent/ring state that must be
        captured together)."""
        if self.paged:
            return self._export_paged_iter(slot, upto, chunk, start=start)
        return iter(self.export_state(slot, upto, chunk, start=start))

    def _export_paged(self, slot: int, upto: int, chunk: int = 0,
                      start: int = 0) -> List[dict]:
        return list(self._export_paged_iter(slot, upto, chunk, start=start))

    def _export_paged_iter(self, slot: int, upto: int, chunk: int = 0,
                           start: int = 0):
        """Page-granular export: whole physical pages, grouped into
        pieces of ``ceil(chunk / page_size)`` pages each (the transfer
        chunk is rounded *up* to page boundaries).  ``start`` (a page
        boundary) drops the leading pages from the export.  The page-id
        table is snapshotted up front (append-only KV: already-exported
        spans are immutable), then pieces are copied out lazily."""
        page = self.page_size
        if start % page:
            raise ValueError(f"export start {start} is not page-aligned")
        table = list(self.allocator.pages_of(slot))
        n_need = pages_for(upto, page)
        if n_need > len(table):
            raise OutOfPages(
                f"slot {slot}: export of {upto} tokens needs {n_need} "
                f"pages, table holds {len(table)}")
        if start >= upto:
            return
        per_piece = pages_for(chunk, page) if chunk else max(1, n_need)
        for p0 in range(start // page, max(1, n_need), per_piece):
            p1 = min(p0 + per_piece, n_need)
            ids = np.asarray(table[p0:p1], np.int32)
            piece = {"span": (p0 * page, min(p1 * page, upto)),
                     "page_size": page, "pages": [],
                     "precision": self.kv_precision.name}
            with spans.span("engine.export") as sp:
                for i in range(len(self.cfg.layer_pattern)):
                    c = self.cache["blocks"][i]
                    # the wire format is token-major (G, n, page, KV, hd)
                    # + (G, n, page) scales, whatever the pool's layout
                    pc = {}
                    for kv in ("k", "v"):
                        scales = c.get(f"{kv}_scales")
                        codes, sc = from_pool_layout(
                            np.asarray(c[f"{kv}_pages"][:, ids]),
                            None if scales is None
                            else np.asarray(scales[:, ids]))
                        pc[kv] = codes
                        if sc is not None:
                            # quantized pool: the per-token dequant
                            # scales ride with their code pages
                            pc[f"{kv}_scales"] = sc
                    piece["pages"].append(pc)
                if sp is not None:
                    sp.set_metadata(rid=self.slot_owner.get(slot, ""),
                                    bytes=_nbytes(piece))
            yield piece
            if p1 >= n_need:
                break

    def _to_pool_format(self, codes, scales):
        """Convert one exported page stack (codes (G,n,page,KV,hd) plus
        optional scales (G,n,page)) into THIS pool's storage format —
        the cross-precision handoff path: a bf16 alpha importing into a
        quantized beta pool quantizes on import, and vice versa."""
        from repro.kernels.ops import quantize_kv
        dst = self.kv_precision
        x = jnp.asarray(codes)
        if scales is not None:
            x = x.astype(jnp.float32) * jnp.asarray(scales)[..., None, None]
        if not dst.quantized:
            pool_dt = self.cache["blocks"][0]["k_pages"].dtype
            return x.astype(pool_dt), None
        return quantize_kv(x, dst.name)

    def _import_paged(self, slot: int, pieces: Sequence[dict]) -> None:
        """Allocate destination pages for every piece, then write each
        layer's pool with ONE scatter over the concatenated page ids —
        per-piece writes would copy the whole pool once per piece.
        Pieces exported from a pool of a different precision are
        converted (dequantized / requantized) page-wise on import."""
        page = self.page_size
        quantized = self.kv_precision.quantized
        all_ids: List[np.ndarray] = []
        nl = len(self.cfg.layer_pattern)
        per_k: List[List] = [[] for _ in range(nl)]
        per_v: List[List] = [[] for _ in range(nl)]
        per_ks: List[List] = [[] for _ in range(nl)]
        per_vs: List[List] = [[] for _ in range(nl)]
        for piece in pieces:
            if piece.get("page_size") != page:
                raise ValueError(
                    f"page_size mismatch: piece ships "
                    f"{piece.get('page_size')}-token pages, engine uses "
                    f"{page}")
            lo, hi = piece["span"]
            if hi <= lo:
                continue
            self.allocator.ensure(slot, hi)
            table = self.allocator.pages_of(slot)
            all_ids.append(np.asarray(
                table[lo // page: pages_for(hi, page)], np.int32))
            src_name = piece.get("precision", "bf16")
            for i, pc in enumerate(piece["pages"]):
                k, v = pc["k"], pc["v"]
                ks, vs = pc.get("k_scales"), pc.get("v_scales")
                if src_name != self.kv_precision.name:
                    k, ks = self._to_pool_format(k, ks)
                    v, vs = self._to_pool_format(v, vs)
                per_k[i].append(k)
                per_v[i].append(v)
                if quantized:
                    per_ks[i].append(ks)
                    per_vs[i].append(vs)
        if not all_ids:
            return
        ids = np.concatenate(all_ids)
        blocks = list(self.cache["blocks"])

        def cat(parts):
            return jnp.concatenate([jnp.asarray(a) for a in parts], axis=1)

        for i in range(len(blocks)):
            nb = {}
            for kv, codes, scales in (("k", per_k[i], per_ks[i]),
                                      ("v", per_v[i], per_vs[i])):
                pages, sc = to_pool_layout(
                    cat(codes), cat(scales) if quantized else None)
                nb[f"{kv}_pages"] = blocks[i][f"{kv}_pages"].at[:, ids].set(
                    pages)
                if quantized:
                    nb[f"{kv}_scales"] = \
                        blocks[i][f"{kv}_scales"].at[:, ids].set(sc)
            blocks[i] = nb
        self.cache = dict(self.cache, blocks=tuple(blocks))

    def import_state(self, slot: int, pieces: Sequence[dict]) -> None:
        with spans.span("engine.import") as sp:
            if sp is not None:
                sp.set_metadata(rid=self.slot_owner.get(slot, ""),
                                bytes=_nbytes(pieces))
            self._import(slot, pieces)

    def _import(self, slot: int, pieces: Sequence[dict]) -> None:
        if self.paged:
            if any("blocks" in p for p in pieces):
                raise ValueError("dense-cache pieces cannot be imported "
                                 "into a paged engine")
            self._import_paged(slot, pieces)
            return
        if any("pages" in p for p in pieces):
            raise ValueError("paged pieces cannot be imported into a "
                             "dense engine")
        cache = self.cache
        for piece in pieces:
            lo, hi = piece["span"]
            for i, bc in enumerate(piece["blocks"]):
                if bc is None:
                    continue
                c = cache["blocks"][i]
                c = {
                    "k": c["k"].at[:, slot, lo:hi].set(jnp.asarray(bc["k"])),
                    "v": c["v"].at[:, slot, lo:hi].set(jnp.asarray(bc["v"])),
                    "pos": c["pos"].at[:, slot, lo:hi].set(jnp.asarray(bc["pos"])),
                }
                blocks = list(cache["blocks"])
                blocks[i] = c
                cache = dict(cache, blocks=tuple(blocks))
            if piece.get("rings"):
                for i, rc in enumerate(piece["rings"]):
                    if rc is None:
                        continue
                    c = cache["blocks"][i]
                    c = {k: c[k].at[:, slot].set(jnp.asarray(v))
                         for k, v in rc.items()}
                    blocks = list(cache["blocks"])
                    blocks[i] = c
                    cache = dict(cache, blocks=tuple(blocks))
            if piece.get("recurrent"):
                for i, rc in enumerate(piece["recurrent"]):
                    if rc is None:
                        continue
                    c = cache["blocks"][i]
                    c = {k: c[k].at[:, slot].set(jnp.asarray(v))
                         for k, v in rc.items()}
                    blocks = list(cache["blocks"])
                    blocks[i] = c
                    cache = dict(cache, blocks=tuple(blocks))
            if piece.get("tail"):
                new_tail = []
                for tc_cur, tc_new in zip(cache["tail"], piece["tail"]):
                    new_tail.append({k: tc_cur[k].at[slot].set(jnp.asarray(v))
                                     for k, v in tc_new.items()})
                cache = dict(cache, tail=tuple(new_tail))
            if piece.get("cross"):
                cache = dict(cache, cross={
                    k: cache["cross"][k].at[:, slot].set(jnp.asarray(v))
                    for k, v in piece["cross"].items()})
        self.cache = cache

    def _kv_itemsize(self) -> int:
        """Itemsize of the dtype the KV cache actually stores — NOT
        ``cfg.dtype``: a quantized page pool holds 1-byte codes, and a
        cache initialised at a different compute dtype differs too."""
        if self.paged:
            return self.cache["blocks"][0]["k_pages"].dtype.itemsize
        for c in self.cache["blocks"]:
            if "k" in c:
                return c["k"].dtype.itemsize
        return jnp.dtype(self.cfg.dtype).itemsize

    def state_bytes(self, upto: int, start: int = 0,
                    as_precision=None) -> int:
        """Bytes a handoff of tokens ``[start, upto)`` moves (for
        transfer modeling; ``start > 0`` is the prefix the destination's
        cache already holds).  Paged engines ship whole pages, so the
        attention term is rounded up to the page size (the padding is
        real wire traffic).  ``as_precision`` prices the same span as if
        the pool stored that format (for savings accounting)."""
        cfg = self.cfg
        total = 0
        if as_precision is not None:
            prec = get_precision(as_precision)
            # unquantized formats store the compute dtype (f32 on the CPU
            # smoke configs), not literal 2-byte bf16
            item = prec.itemsize if prec.quantized \
                else jnp.dtype(cfg.dtype).itemsize
            per_tok = 2 * cfg.n_kv_heads * cfg.hd * item
            quantized = self.paged and prec.quantized
        else:
            per_tok = 2 * cfg.n_kv_heads * cfg.hd * self._kv_itemsize()
            quantized = self.paged and self.kv_precision.quantized
        if quantized:
            # k + v per-token f32 dequant scales travel with the codes
            per_tok += 2 * 4
        if self.paged:
            upto_attn = (pages_for(upto, self.page_size)
                         - start // self.page_size) * self.page_size
        else:
            upto_attn = upto - start
        for kind in (list(cfg.layer_pattern) * cfg.n_groups)[: cfg.n_layers]:
            if kind == "attn":
                total += upto_attn * per_tok
            elif kind == "local_attn":
                total += min(upto, cfg.window or upto) * per_tok
            elif kind == "ssd":
                total += cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
            elif kind == "rglru":
                total += cfg.lru_dim * 4
        return total
