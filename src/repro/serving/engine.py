"""Continuous-batching serving engine over a real JAX model.

Runs the same controller stack as the simulator (Telemetry -> Policy ->
BlockManager, DESIGN §1) with actual jit-compiled prefill/decode steps and
wall-clock TBT feedback. Batch sizes are bucketized (TPU/XLA static shapes —
DESIGN §3): the decode step runs on the smallest compiled bucket >= active
requests, with inactive rows masked via position -1.

PD fusion (DESIGN §6) runs `n_prefill_lanes` spare physical cache rows past
the decode buckets; each scheduling interval the controller's chunk budget
is packed across occupied lanes and same-size lane chunks are batched into
one jit'd multi-row prefill graph. Finished lanes promote into the compacted
decode region.

Intended for reduced-config models on CPU (tests, Fig-3-style curves) and as
the production template for TPU serving (launch/serve.py).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig, ServeConfig
from repro.core.batching import bucketize, make_policy
from repro.core.lanes import lane_order, pack_chunks
from repro.core.memory_model import MemoryModel, kv_shard_factor
from repro.core.telemetry import Telemetry
from repro.models.model import Model
from repro.serving.cost_model import CostModel, PROFILES
from repro.serving.kv_cache import (BlockManager, prefix_cache_supported,
                                    swap_supported)
from repro.serving.request import Request, RequestState
from repro.serving.sampling import sample


# host spans on the profiler's clock (DESIGN §16), one per phase of
# step(). jax.profiler annotations: no-ops unless a trace is running, and
# never inside a jitted function (they would fire at trace time only)
SPANS = ("engine.step", "engine.schedule", "engine.admit", "engine.preempt",
         "engine.prefill", "engine.decode", "engine.release",
         "engine.retire.fence", "engine.retire.readback",
         "engine.retire.stamp")
(SPAN_STEP, SPAN_SCHEDULE, SPAN_ADMIT, SPAN_PREEMPT, SPAN_PREFILL,
 SPAN_DECODE, SPAN_RELEASE, SPAN_FENCE, SPAN_READBACK, SPAN_STAMP) = SPANS
_span = jax.profiler.TraceAnnotation


def _batch_axis(name: str) -> int:
    return 0 if name == "pos" else 1


def cache_take(cache: Dict[str, Any], start: int, n: int) -> Dict[str, Any]:
    return {k: jax.lax.slice_in_dim(v, start, start + n, axis=_batch_axis(k))
            for k, v in cache.items()}


def cache_put(cache: Dict[str, Any], sub: Dict[str, Any],
              start: int) -> Dict[str, Any]:
    return {k: jax.lax.dynamic_update_slice_in_dim(
        v, sub[k], start, axis=_batch_axis(k)) for k, v in cache.items()}


def cache_copy_row(cache: Dict[str, Any], dst: int, src: int) -> Dict[str, Any]:
    out = {}
    for k, v in cache.items():
        ax = _batch_axis(k)
        row = jax.lax.index_in_dim(v, src, axis=ax, keepdims=False)
        idx = [slice(None)] * v.ndim
        idx[ax] = dst
        out[k] = v.at[tuple(idx)].set(row)
    return out


def state_clear_row(cache: Dict[str, Any], i: int) -> Dict[str, Any]:
    """Zero the per-slot state of one physical row — all paged mode needs
    (`pos` lives in the block pool there and is cleared when blocks free,
    DESIGN §9)."""
    out = dict(cache)
    for k in ("conv", "rec", "ssm", "held"):
        if k in cache:
            out[k] = cache[k].at[:, i].set(0)
    return out


def cache_clear_row(cache: Dict[str, Any], i: int) -> Dict[str, Any]:
    out = state_clear_row(cache, i)
    if "pos" in cache:
        out["pos"] = cache["pos"].at[i].set(-1)
    return out


def _i32(x) -> jnp.ndarray:
    """Host ints to a device int32 array, compiling nothing: `jnp.array`
    of a list compiles a conversion once per shape, and a shape first met
    while serving would compile there."""
    return jnp.asarray(np.asarray(x, np.int32))


@jax.jit
def _clear_pos_rows(pos, blocks):
    """Clear the pos-pool rows of `blocks`; ids past the pool's end (the
    padding of a fixed-length id vector) drop."""
    return pos.at[blocks].set(-1, mode="drop")


# per-slot state keys in paged mode: everything except the k/v/pos pools
_POOL_KEYS = ("k", "v", "pos")


@dataclasses.dataclass
class _StepRec:
    """One dispatched interval's retirement record (DESIGN §14).

    Dispatch runs every value-independent decision — admission, lane
    packing, grow/finish/preempt bookkeeping, block-table edits — and
    parks the value-DEPENDENT residue here: the device futures to fence
    on, the output-token placeholders to patch, and the telemetry feeds
    that must not land before the step's results exist."""
    #: device futures: "dec" sampled-token vector, "first" argmax scalars
    #: (promotions / non-chunked prefills), "probe" the last dispatched
    #: logits (fence anchor for prefill-only intervals)
    payload: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: (request, output index, life generation, "d"|"f", payload row)
    patches: List[Tuple[Request, int, int, str, int]] = \
        dataclasses.field(default_factory=list)
    #: (request, life generation, feed on_first_token, queue_s,
    #: prefill_start) TTFT stamps
    firsts: List[Tuple[Request, int, bool, float, float]] = \
        dataclasses.field(default_factory=list)
    #: (request, output length) completion stamps, finish order preserved
    completions: List[Tuple[Request, int]] = \
        dataclasses.field(default_factory=list)
    #: lane -> packed chunk tokens: the on_prefill_interval feed
    lane_tokens: Optional[Dict[int, int]] = None
    #: (request, life generation, state row) of every row this interval
    #: ran, whose held-expert counts the "held" payload carries (DESIGN §17)
    held_rows: List[Tuple[Request, int, int]] = \
        dataclasses.field(default_factory=list)
    n_decode: int = 0
    dispatched: bool = False


def cache_gather(cache: Dict[str, Any], rows) -> Dict[str, Any]:
    """Gather a (possibly non-contiguous) set of physical rows into a
    compact sub-cache — the multi-lane prefill batch (DESIGN §6) and the
    paged per-slot state (DESIGN §9). Out-of-bounds rows (the paged
    padding sentinel) read as zeros — NOT the jnp.take default NaN fill,
    which would trip JAX_DEBUG_NANS on every padded step."""
    return {k: jnp.take(v, rows, axis=_batch_axis(k), mode="fill",
                        fill_value=0)
            for k, v in cache.items()}


def cache_scatter(cache: Dict[str, Any], sub: Dict[str, Any],
                  rows) -> Dict[str, Any]:
    """Scatter a gathered sub-cache back into its physical rows."""
    out = {}
    for k, v in cache.items():
        if _batch_axis(k) == 0:
            out[k] = v.at[rows].set(sub[k])
        else:
            out[k] = v.at[:, rows].set(sub[k])
    return out


class Engine:
    def __init__(self, model: Model, params, serve: ServeConfig,
                 max_context: int = 256,
                 buckets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64),
                 prefill_chunk: int = 32, enc_len: int = 0, seed: int = 0,
                 temperature: float = 0.0,
                 cost: Optional[CostModel] = None, mesh=None):
        self.model = model
        self.cfg: ModelConfig = model.cfg
        self.serve = serve
        self.max_context = max_context
        self.buckets = tuple(sorted(b for b in buckets if b <= serve.b_max)) \
            or (serve.b_max,)
        self.max_slots = max(self.buckets)
        self.prefill_chunk = prefill_chunk
        self.params = params
        self.enc_len = enc_len
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)

        # mesh-sharded serving (DESIGN §12): params tensor-parallel over
        # "model" (§5 name rules, data axes replicated), the KV pool
        # sharded over "model" on kv-heads — per-chip pool quantities
        # scale by the effective shard count
        if mesh is None and serve.mesh_shape:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(serve.mesh_shape)
        self.mesh = mesh
        self.model_shards = 1
        if mesh is not None and "model" in mesh.axis_names:
            self.model_shards = kv_shard_factor(self.cfg,
                                                int(mesh.shape["model"]))

        # n_prefill_lanes spare physical rows: PD-fusion prefilling requests
        # live outside every decode bucket so masked decode steps can never
        # touch their (stateful) cache rows (DESIGN §6)
        self.n_lanes = max(1, serve.n_prefill_lanes)
        eta = serve.kv_pool_tokens or self.max_slots * max_context
        # per-chip scaling (DESIGN §12) applies to EXPLICIT budgets only:
        # the slot-derived fallback is already the maximum the block
        # tables can address, so scaling it by the shard count would
        # allocate pool blocks no table could ever reference
        pool_shards = self.model_shards if serve.kv_pool_tokens else 1
        self.mem = MemoryModel(self.cfg, hbm_budget_bytes=0,
                               eps_m=serve.eps_m,
                               block_size=serve.block_size, eta_tokens=eta,
                               model_shards=pool_shards)
        self.paged = serve.paged_kv
        # ref-counted prefix sharing (DESIGN §10): needs the paged pool (the
        # contiguous layout has no shareable physical blocks) and a family
        # whose prefix lives entirely in attention K/V blocks
        self.prefix = (serve.prefix_cache and self.paged
                       and prefix_cache_supported(self.cfg)
                       and self.mem.bytes_per_token != 0)
        # two-tier swap space (DESIGN §11): needs the paged pool (swap moves
        # physical blocks) and a family whose per-request state lives
        # entirely in the K/V block pools
        self.swap = (serve.swap_space_blocks > 0
                     and serve.preempt != "recompute" and self.paged
                     and swap_supported(self.cfg)
                     and self.mem.bytes_per_token != 0)
        self.blocks = BlockManager(self.mem.eta, serve.block_size,
                                   prefix_cache=self.prefix,
                                   swap_space_blocks=serve.swap_space_blocks
                                   if self.swap else 0)
        # swap-vs-recompute crossover (DESIGN §11): the same CostModel the
        # simulator twin uses; only the PCIe/prefill time laws are read
        self.cost = cost or CostModel(self.cfg, PROFILES["a100x8"])
        self.n_slots = self.max_slots + self.n_lanes
        # per-request block-table width: enough blocks for a full context
        self.max_blocks = -(-max_context // serve.block_size)
        if self.paged:
            # physically paged cache (DESIGN §9): K/V pools sized by the
            # allocator's block count — BlockManager's tables ARE the
            # storage map. Requests pin a per-slot state row for life.
            cache_fn = lambda: model.init_paged_cache(  # noqa: E731
                self.n_slots, self.mem.num_blocks, serve.block_size,
                enc_len=enc_len)
            self._free_slots = list(range(self.n_slots))
        else:
            cache_fn = lambda: model.init_cache(  # noqa: E731
                self.n_slots, max_context, enc_len=enc_len,
                prefill_chunk=prefill_chunk)
        self.cache = self._init_cache_on_mesh(cache_fn)
        if self.mesh is not None:
            self._shard_state()
        self.tel = Telemetry()
        self.policy = make_policy(serve, self.mem)

        self.waiting: List[Request] = []
        self.active: List[Request] = []          # compact: slot i = active[i]
        # PD fusion (DESIGN §6): admitted requests being chunk-prefilled.
        # A request with r.lane >= 0 owns physical row max_slots + r.lane;
        # the rest queue for a free lane.
        self.prefilling: List[Request] = []
        self.lanes: List[Optional[Request]] = [None] * self.n_lanes
        # two-tier swap (DESIGN §11): offloaded requests awaiting swap-in;
        # admission drains this queue before `waiting`
        self.swapped: List[Request] = []
        self.now0 = time.perf_counter()
        # summary()'s serving window: first submitted arrival to the last
        # retirement, so warm-up and compiles before it are not counted
        self._first_arrival: Optional[float] = None
        self._last_retire: Optional[float] = None
        self._next_rid = 0
        self.total_decoded = 0
        self.total_finished = 0
        self.admitted_total = 0   # successful admissions from `waiting`
        self.preemptions = 0      # evictions, recompute + swap-out alike
        self.oom_events = 0       # admission refusals at the watermark
        self.rejected = 0         # requests too large for the pool, dropped
        # per-request goodput SLOs (DESIGN §15): verdicts stamp at
        # retirement (timestamps are final there); rejected requests
        # count against attainment
        self.sla_requests_met = 0
        self.goodput_tokens = 0
        self.swap_outs = 0        # victims offloaded to the host pool
        self.swap_ins = 0         # offloaded requests restored
        self.swap_out_bytes = 0
        self.swap_in_bytes = 0
        self.swap_wait_trace: List[float] = []   # per-round-trip latency (s)
        # host-side swap storage: one numpy row set per host block, shaped
        # like the device pools (k/v block axis 1, pos axis 0)
        self._host_pool: Dict[str, np.ndarray] = {}
        if self.swap:
            nhb = serve.swap_space_blocks
            for k in _POOL_KEYS:
                v = self.cache.get(k)
                if v is None:
                    continue
                shape = ((v.shape[0], nhb) + v.shape[2:]) if k != "pos" \
                    else (nhb,) + v.shape[1:]
                self._host_pool[k] = np.zeros(shape, v.dtype)
        # contiguous-layout row copies (promotion/compaction/eviction);
        # stays 0 under paged_kv — the paged layout's headline win
        self.copy_rows = 0
        self.copy_bytes = 0
        self._row_bytes = 0 if self.paged else sum(
            int(v.size // v.shape[_batch_axis(k)]) * v.dtype.itemsize
            for k, v in self.cache.items())
        # per-block pool bytes: the unit a COW duplication copies (DESIGN §10)
        self._blk_bytes = sum(
            int(v.size // v.shape[0 if k == "pos" else 1]) * v.dtype.itemsize
            for k, v in self.cache.items() if k in _POOL_KEYS) \
            if self.paged else 0
        self.decode_steps = 0
        self.batch_trace: List[int] = []
        self.tbt_trace: List[float] = []
        # per-request TTFT (queue wait + prefill service), for the p90/mean
        # twins of SimResult (DESIGN §7 differential harness)
        self.ttft_trace: List[float] = []
        # SLA attainment, sim-mirrored: decode steps within d_sla + eps_d
        self._sla_ok = 0
        self._sla_steps = 0
        # per-interval packed prefill tokens (packer audit: sum of lane
        # chunks each fused interval; each entry <= that interval's budget)
        self.prefill_tokens_trace: List[int] = []
        # async dispatch-ahead pipeline (DESIGN §14): up to overlap_depth
        # dispatched intervals stay un-fenced while the host schedules the
        # next one against the live allocator + stale telemetry feeds;
        # 0 = the synchronous loop (each interval retires in its own call)
        self.overlap_depth = max(0, int(serve.overlap_depth))
        self._inflight: "collections.deque[_StepRec]" = collections.deque()
        # rid -> device scalar of the request's newest not-yet-retired
        # token: the next decode step's input, spliced in without readback
        self._pending_tok: Dict[int, Any] = {}
        # rid -> life generation, bumped by _evict: retirement drops
        # patches recorded against an earlier (cleared) life
        self._gen: Dict[int, int] = {}
        # host-vs-device interval split (DESIGN §14): per step() call,
        # device_s = the retirement fence wait, host_s = the remainder
        self.step_host_trace: List[float] = []
        self.step_device_trace: List[float] = []
        # held-expert routing counter (DESIGN §17): the paged cache's
        # per-slot `held` counts ride each retirement's readback
        self.held = self.paged and "held" in self.cache
        self.held_selections_total = 0

        self._decode_jit = self._mesh_call(jax.jit(self._decode_fn))
        self._prefill_jit = self._mesh_call(jax.jit(self._prefill_fn))
        self._prefill_lanes_jit = self._mesh_call(
            jax.jit(self._prefill_lanes_fn))
        # donate the cache operand (arg 5 in both paged fns) so XLA updates
        # the K/V pools in place instead of copying them every step — the
        # whole point of the paged layout. CPU doesn't implement donation
        # (it would just warn), so only donate on accelerators.
        donate = () if jax.default_backend() == "cpu" else (5,)
        self._decode_paged_jit = self._mesh_call(
            jax.jit(self._decode_paged_fn, donate_argnums=donate))
        self._prefill_paged_jit = self._mesh_call(
            jax.jit(self._prefill_paged_fn, donate_argnums=donate))
        # device-table cache keyed by (call-site, shape): fused intervals
        # alternate between the prefill-group and decode-bucket tables
        # (which can share a shape), so a single slot would thrash
        self._tables_dev: Dict[Tuple[str, Tuple[int, int]],
                               Tuple[np.ndarray, jnp.ndarray]] = {}

    # -- mesh-sharded serving (DESIGN §12) -------------------------------------
    def _init_cache_on_mesh(self, cache_fn):
        """Allocate the serving cache — directly under its mesh shardings
        when a mesh is set. The paged pool is `model_shards`× the per-chip
        budget, so materializing it on one device first (then resharding)
        would OOM exactly the chips §12 is sized for; jit with
        out_shardings creates each shard in place."""
        if self.mesh is None:
            return cache_fn()
        from repro.distributed.sharding import serve_cache_shardings
        shardings = serve_cache_shardings(
            jax.eval_shape(cache_fn), self.cfg, self.mesh)
        with self.mesh:
            return jax.jit(cache_fn, out_shardings=shardings)()

    def _shard_state(self):
        """Place params on the mesh: TP over "model" (§5 rules, data axes
        replicated). Params already created under `serve_param_shardings`
        (as `launch/serve.py` creates them, so a model that does not fit
        one chip never lands on one) pass through unchanged; others are
        resharded (`device_put`)."""
        from repro.distributed.sharding import serve_param_shardings
        self.params = jax.device_put(
            self.params,
            serve_param_shardings(self.params, self.cfg, self.mesh))

    def _mesh_call(self, jf):
        """Wrap a jit'd step so it runs inside the mesh context with the
        ambient serving mesh installed (routes the paged kernel through
        its shard_map wrapper at trace time — DESIGN §12). No-op without
        a mesh: the single-device engine is byte-for-byte untouched."""
        if self.mesh is None:
            return jf

        from repro.distributed import sharding as _sharding

        def call(*args):
            prev = _sharding.set_serving_mesh(self.mesh)
            try:
                with self.mesh:
                    return jf(*args)
            finally:
                _sharding.set_serving_mesh(prev)

        return call

    # -- jit'd steps ----------------------------------------------------------
    def _decode_fn(self, params, tokens, seq_lens, cache):
        return self.model.decode_step(params, tokens, seq_lens, cache)

    def _prefill_fn(self, params, tokens, positions, cache, extras):
        return self.model.prefill(params, tokens, positions, cache, extras)

    def _prefill_lanes_fn(self, params, tokens, positions, cache, rows):
        """Multi-row lane prefill: gather the lane rows into one batch, run
        a single prefill graph, scatter the rows back (DESIGN §6). Compiles
        one graph per (n_rows, chunk_len) shape."""
        sub = cache_gather(cache, rows)
        logits, sub = self.model.prefill(params, tokens, positions, sub, None)
        return logits, cache_scatter(cache, sub, rows)

    # -- paged-mode jit'd steps (DESIGN §9) ------------------------------------
    # K/V pools + the pos map are global (no batch axis); per-slot state is
    # gathered by the requests' pinned rows, run, and scattered back. Row
    # index n_slots is the padding sentinel: its gathers read as zeros
    # (cache_gather fills OOB) and its scatters drop.
    def _split_state(self, cache):
        return {k: v for k, v in cache.items() if k not in _POOL_KEYS}

    def _merge_paged(self, cache, sub, rows):
        out = dict(cache)
        for k in _POOL_KEYS:
            if k in sub:
                out[k] = sub[k]
        state_new = self._split_state(sub)
        if state_new:
            out.update(cache_scatter(
                {k: cache[k] for k in state_new}, state_new, rows))
        return out

    def _decode_paged_fn(self, params, tokens, seq_lens, tables, rows, cache):
        sub = cache_gather(self._split_state(cache), rows)
        for k in _POOL_KEYS:
            if k in cache:
                sub[k] = cache[k]
        logits, sub = self.model.decode_step_paged(
            params, tokens, seq_lens, tables, sub)
        return logits, self._merge_paged(cache, sub, rows)

    def _prefill_paged_fn(self, params, tokens, positions, tables, rows,
                          cache, extras):
        sub = cache_gather(self._split_state(cache), rows)
        for k in _POOL_KEYS:
            if k in cache:
                sub[k] = cache[k]
        logits, sub = self.model.prefill_paged(
            params, tokens, positions, tables, sub, extras)
        return logits, self._merge_paged(cache, sub, rows)

    # -- paged-mode host-side helpers ------------------------------------------
    def _tables_for(self, reqs, pad_to: int = 0,
                    kind: str = "prefill") -> jnp.ndarray:
        """Device block tables for a batch: row i holds request i's physical
        block ids from the BlockManager, -1-padded (DESIGN §9). Tables only
        change on block grow / membership changes (at most once per
        block_size steps per request), so the device upload is reused while
        the host copy is unchanged."""
        n = max(pad_to, len(reqs), 1)
        tbl = np.full((n, self.max_blocks), -1, np.int32)
        for i, r in enumerate(reqs):
            ids = self.blocks.tables.get(r.rid, [])
            tbl[i, :len(ids)] = ids
        key = (kind, tbl.shape)
        cached = self._tables_dev.get(key)
        if cached is not None and np.array_equal(cached[0], tbl):
            return cached[1]
        dev = jnp.asarray(tbl)
        self._tables_dev[key] = (tbl, dev)
        return dev

    def _release_blocks(self, freed: List[int]):
        """Clear the pos-pool rows of freed blocks so a future tenant never
        sees the previous request's stale positions (DESIGN §9)."""
        if self.paged and freed and "pos" in self.cache:
            # one program for every freed-block count: the ids go in
            # max_blocks at a time, padded past the pool's end, so no
            # count compiles anew while serving (warmed in `warmup`)
            with _span(SPAN_RELEASE, blocks=len(freed)):
                self._clear_blocks(freed)

    def _clear_blocks(self, freed: List[int]):
        pos = self.cache["pos"]
        m = self.max_blocks
        for i in range(0, len(freed), m):
            ids = np.full((m,), pos.shape[0], np.int32)
            part = freed[i:i + m]
            ids[:len(part)] = part
            pos = _clear_pos_rows(pos, jnp.asarray(ids))
        self.cache = dict(self.cache, pos=pos)

    def _drain_released(self):
        """Clear pos rows of blocks the allocator evicted from the prefix
        cache for reuse (DESIGN §10): a new tenant must never see the cached
        tenant's stale positions."""
        self._release_blocks(self.blocks.take_released())

    def _cow_blocks(self, pairs):
        """Apply copy-on-write block duplications the allocator ordered
        (`BlockManager.cow_range`): copy the K/V/pos pool rows from the
        shared source block into the private copy. Suffix-aligned mapping
        keeps this off the steady-state path (DESIGN §10)."""
        if not pairs:
            return
        out = dict(self.cache)
        for src, dst in pairs:
            for k in ("k", "v"):
                if k in out:
                    out[k] = out[k].at[:, dst].set(out[k][:, src])
            if "pos" in out:
                out["pos"] = out["pos"].at[dst].set(out["pos"][src])
            self.copy_bytes += self._blk_bytes
        self.cache = out

    def _free_request(self, r) -> None:
        """Release a request's blocks (+ slot/pos rows in paged mode).
        Under prefix sharing this is a decref: registered blocks stay
        resident as evictable cache and keep their pos rows (DESIGN §10)."""
        freed = self.blocks.free(r.rid)
        # the request's newest token no longer feeds a next decode step;
        # pending patches read the retirement record's payload directly
        self._pending_tok.pop(r.rid, None)
        if self.paged:
            self._release_blocks(freed)
            if r.slot >= 0:
                self._free_slots.append(r.slot)
                r.slot = -1

    def _copy_row(self, dst: int, src: int):
        self.cache = cache_copy_row(self.cache, dst, src)
        self.copy_rows += 1
        self.copy_bytes += self._row_bytes

    # -- public API -------------------------------------------------------------
    def submit(self, prompt_tokens: List[int], max_new_tokens: int = 0,
               extras: Optional[Dict[str, jnp.ndarray]] = None,
               arrival_time: Optional[float] = None) -> Request:
        t = arrival_time if arrival_time is not None else self._now()
        mx = max_new_tokens or self.serve.max_new_tokens
        mx = min(mx, self.max_context - len(prompt_tokens) - 1)
        r = Request(rid=self._next_rid, arrival_time=t,
                    prompt_tokens=list(prompt_tokens), max_new_tokens=mx)
        self._next_rid += 1
        if self._first_arrival is None:
            self._first_arrival = t
        r.extras = extras
        self.waiting.append(r)
        self.tel.on_arrival(t, r.prompt_len)
        return r

    def warmup(self):
        """Compile decode buckets + prefill graphs so TBT feedback is clean.

        Covers every full-chunk shape: the single-row graph plus one
        multi-row lane graph per group size 2..n_prefill_lanes (tail chunks
        still compile on first use — one graph per distinct tail length)."""
        if self.paged:
            # all-padding warmup batches: positions -1 write nothing, table
            # entries -1 read nothing, sentinel rows scatter-drop. The cache
            # operand is donated, so rebind the returned (content-identical)
            # cache each call.
            for b in self.buckets:
                toks = jnp.zeros((b,), jnp.int32)
                lens = jnp.full((b,), -1, jnp.int32)
                tables = jnp.full((b, self.max_blocks), -1, jnp.int32)
                rows = jnp.full((b,), self.n_slots, jnp.int32)
                logits, self.cache = self._decode_paged_jit(
                    self.params, toks, lens, tables, rows, self.cache)
                jax.block_until_ready(logits)
            for g in range(1, self.n_lanes + 1):
                tt = jnp.zeros((g, self.prefill_chunk), jnp.int32)
                pos = jnp.full((g, self.prefill_chunk), -1, jnp.int32)
                tables = jnp.full((g, self.max_blocks), -1, jnp.int32)
                rows = jnp.full((g,), self.n_slots, jnp.int32)
                logits, self.cache = self._prefill_paged_jit(
                    self.params, tt, pos, tables, rows, self.cache, None)
                jax.block_until_ready(logits)
            if "pos" in self.cache:
                # `_release_blocks`' one program; a block id past the
                # pool's end clears nothing
                self._clear_blocks([self.cache["pos"].shape[0]])
            if self.held:
                # the readback's copy program, and the clear of a slot's
                # counts when a request is pinned to it
                self._held_snapshot()
                self.cache = state_clear_row(self.cache, 0)
            return
        for b in self.buckets:
            sub = cache_take(self.cache, 0, b)
            toks = jnp.zeros((b,), jnp.int32)
            lens = jnp.full((b,), -1, jnp.int32)
            jax.block_until_ready(self._decode_jit(self.params, toks, lens, sub))
        sub = cache_take(self.cache, 0, 1)
        tt = jnp.zeros((1, self.prefill_chunk), jnp.int32)
        pos = jnp.full((1, self.prefill_chunk), -1, jnp.int32)
        jax.block_until_ready(
            self._prefill_jit(self.params, tt, pos, sub, None))
        for g in range(2, self.n_lanes + 1):
            rows = jnp.arange(self.max_slots, self.max_slots + g, dtype=jnp.int32)
            tt = jnp.zeros((g, self.prefill_chunk), jnp.int32)
            pos = jnp.full((g, self.prefill_chunk), -1, jnp.int32)
            logits, _ = self._prefill_lanes_jit(self.params, tt, pos,
                                                self.cache, rows)
            jax.block_until_ready(logits)

    def _now(self) -> float:
        return time.perf_counter() - self.now0

    # -- scheduling interval -------------------------------------------------------
    def step(self) -> bool:
        """One scheduling interval. Returns False when fully idle.

        Async dispatch-ahead pipeline (DESIGN §14): schedule interval N
        against telemetry whose TBT/TTFT/throughput feeds are stale by up
        to `overlap_depth` un-retired intervals (Alg 1 tolerates stale
        snapshots — pool occupancy is always read live from the
        allocator), dispatch N's prefill/decode graphs WITHOUT fencing,
        then retire the oldest in-flight interval(s) until at most
        `overlap_depth` device steps remain in flight. Depth 0 retires N
        before returning — the synchronous loop, interval for interval.

        Each phase runs inside its host span (DESIGN §16), the whole call
        inside `engine.step` numbered by interval.
        """
        with jax.profiler.StepTraceAnnotation(
                SPAN_STEP, step_num=len(self.step_host_trace)):
            return self._step()

    def _step(self) -> bool:
        if not self.waiting and not self.active and not self.prefilling \
                and not self.swapped:
            # pipeline drain: retirement only patches token values,
            # timestamps and telemetry — it never creates schedulable
            # work — so the drained call still reports idle and run()'s
            # step count matches the synchronous loop exactly
            while self._inflight:
                self._retire_step()
            return False
        t0 = time.perf_counter()
        with _span(SPAN_SCHEDULE):
            tel = self.tel.snapshot(
                now=self._now(),
                n_prefill=len(self.waiting) + len(self.prefilling),
                n_decode=len(self.active),
                free_tokens=self.blocks.free_tokens,
                logical_used_tokens=self.blocks.logical_used_tokens,
                physical_used_tokens=self.blocks.physical_used_tokens,
                swapped_tokens=self.blocks.swapped_tokens)
            decision = self.policy.step(tel)
            # sim-mirrored admission (DESIGN §7): bucketize the
            # controller's cap to the compiled batch buckets and apply the
            # shared BlockManager.admission_verdict (vLLM 1% watermark +
            # unservable rejection), counting watermark refusals as
            # oom_events. bucketize rounds UP to the floor bucket when b_t
            # is below the smallest compiled one — admitted rows must
            # still respect the controller's decision (the graph pads,
            # admission must not)
            cap = bucketize(decision.max_batch, self.serve.batch_buckets) \
                if self.serve.batch_buckets else decision.max_batch
            cap = min(cap, decision.max_batch, self.max_slots)
        rec = _StepRec()
        with _span(SPAN_ADMIT, waiting=len(self.waiting)):
            self._admit(cap, rec)
        with _span(SPAN_PREEMPT):
            self._preempt_if_needed()
        if self.serve.chunked_prefill:
            # PD fusion: one fused interval = a prefill chunk (within the
            # controller's token budget) + the decode batch; TBT accounts
            # for both (the paper's adaptive-chunk-size scenario)
            budget = decision.chunk_budget \
                or self.serve.chunk_budget_tokens
            if budget <= 0 and self.prefilling and not self.active:
                # nothing decoding and no token budget: the engine would
                # spin no-op intervals forever — make minimum progress on
                # one full chunk instead of livelocking
                budget = self.prefill_chunk
            if self.prefilling:
                with _span(SPAN_PREFILL, **self._span_args(
                        budget=budget, lanes_busy=min(
                            self.n_lanes, len(self.prefilling)))):
                    self._advance_prefill(budget, rec)
            if self.active:
                self._decode_once(rec)
        elif self.active:
            self._decode_once(rec)
        if rec.dispatched:
            if rec.held_rows:
                rec.payload["held"] = self._held_snapshot()
            self._inflight.append(rec)
        # retire down to the pipeline depth: the fence wait is the
        # interval's device time; everything else this call did is host
        # work the in-flight step(s) just hid
        device_s = 0.0
        while len(self._inflight) > self.overlap_depth:
            device_s += self._retire_step()
        host_s = (time.perf_counter() - t0) - device_s
        self.step_host_trace.append(host_s)
        self.step_device_trace.append(device_s)
        # fed live, not lagged: the split is produced by retirement
        # itself, not by the interval being scheduled (DESIGN §14)
        self.tel.on_interval(host_s, device_s)
        return True

    def _held_snapshot(self):
        """A copy of the per-slot held-expert counts, taken after the
        interval's last dispatch: the next step donates the cache, and the
        copy is read in the retirement's one readback (DESIGN §17)."""
        return jnp.copy(self.cache["held"])

    def _span_args(self, **kw) -> Dict[str, Any]:
        """A prefill/decode span's args; with held experts, also the
        held-expert selections retired so far (DESIGN §17)."""
        if self.held:
            kw["held_selections"] = self.held_selections_total
        return kw

    def _admit(self, cap: int, rec: _StepRec) -> None:
        """Swap-in drain, then admission from `waiting`, up to `cap`
        requests holding a slot or lane."""
        # swap-in drain (DESIGN §11): offloaded requests re-enter BEFORE
        # any new admission — they resume decode without re-prefill, and
        # while any remain, `waiting` is held back so fresh arrivals can
        # never starve the swap-in path of pool headroom
        while self.swapped \
                and len(self.active) + len(self.prefilling) < cap:
            if not self._swap_in_next():
                self.oom_events += 1
                break

        while self.waiting and not self.swapped \
                and len(self.active) + len(self.prefilling) < cap:
            r = self.waiting[0]
            need = r.prompt_len + 1
            if self.mem.bytes_per_token == 0:
                need = self.serve.block_size
            # prefix sharing (DESIGN §10): map every indexed full prompt
            # block into the table first (zero copies), then gate admission
            # on the unmatched suffix only — rolled back on refusal
            cached = 0
            if self.prefix and r.prompt_tokens:
                cached = self.blocks.acquire_prefix(r.rid, r.prompt_tokens)
            have = len(self.blocks.tables.get(r.rid, ()))
            nb = self.blocks.blocks_needed(0, need, r.rid)
            mb = self.max_blocks - have
            verdict = "reject" if mb <= 0 and nb > 0 \
                else self.blocks.admission_verdict(nb, mb)
            if verdict != "admit":
                if cached:
                    self.blocks.free(r.rid)
                if verdict == "reject":
                    # no pool state can ever hold it (bigger than the pool
                    # minus the watermark, or than the block-table width):
                    # drop it rather than wedging the queue behind it
                    self.waiting.pop(0)
                    r.state = RequestState.FINISHED
                    r.rejected = True
                    r.finish_time = self._now()
                    # goodput verdict (DESIGN §15): a dropped request
                    # counts against attainment, never for it
                    r.stamp_sla(self.serve.ttft_sla_s, self.serve.tbt_sla_ms)
                    self.rejected += 1
                    continue
                self.oom_events += 1
                break
            self.blocks.allocate(r.rid, 0, need)
            r.admit_time = self._now()
            if self.prefix:
                self.blocks.note_prefix_query(r.prompt_len, cached)
            r.cached_prefix_len = cached
            self.waiting.pop(0)
            self.admitted_total += 1
            if self.serve.chunked_prefill:
                r.state = RequestState.PREFILLING
                r.prefill_pos = cached
                self.prefilling.append(r)
            else:
                self._prefill_request(r, rec)
        self._drain_released()

    # -- PD fusion internals (DESIGN §6) ---------------------------------------
    def _fill_lanes(self):
        """Assign queued prefilling requests to free lanes (sticky: a lane
        keeps its request until promotion)."""
        queued = [(None, r) for r in self.prefilling if r.lane < 0]
        if not queued:
            return
        queued = lane_order(self.serve.prefill_pack, queued)
        for j in range(self.n_lanes):
            if self.lanes[j] is not None:
                continue
            if not queued:
                break
            _, r = queued.pop(0)
            if self.paged:
                # pin a state row for the request's whole life: promotion
                # will be a pure bookkeeping move (DESIGN §9)
                slot = self._free_slots.pop()
                self.cache = state_clear_row(self.cache, slot)
            else:
                slot = self.max_slots + j
                self.cache = cache_clear_row(self.cache, slot)
            r.lane = j
            r.slot = slot
            self.lanes[j] = r

    def _advance_prefill(self, budget_tokens: int, rec: _StepRec) -> None:
        """Advance up to n_prefill_lanes prefilling requests by one chunk
        each, within the interval's token budget (shared packer:
        core.lanes.pack_chunks). Dispatch-only (DESIGN §14): no fence —
        the chunk logits land in `rec` and promoted first tokens are
        patched at retirement."""
        if not self.prefilling or budget_tokens <= 0:
            return
        self._fill_lanes()
        plan = pack_chunks(self.serve.prefill_pack, self.lanes,
                           budget_tokens, self.prefill_chunk)
        if not plan:
            return
        for _, r, _ in plan:
            if r.prefill_start_time < 0:
                r.prefill_start_time = self._now()
        if self.held:
            rec.held_rows.extend((r, self._gen.get(r.rid, 0), r.slot)
                                 for _, r, _ in plan)
        if self.prefix:
            # COW guard (DESIGN §10): a shared block in this chunk's write
            # range gets a private copy first — structurally unreachable
            # with block-aligned suffixes, kept as the safety invariant
            for _, r, take in plan:
                self._cow_blocks(self.blocks.cow_range(
                    r.rid, r.prefill_pos, r.prefill_pos + take))

        # batch same-size chunks into one multi-row graph; first chunks
        # carrying extras (image/audio embeddings differ per request) run
        # as single-row calls on the existing contiguous path
        single = [(j, r, t) for j, r, t in plan
                  if r.prefill_pos == 0 and getattr(r, "extras", None)
                  is not None]
        single_lanes = {j for j, _, _ in single}
        groups: Dict[int, list] = {}
        for j, r, t in plan:
            if j in single_lanes:
                continue
            groups.setdefault(t, []).append((j, r, t))

        last_logits: Dict[int, Any] = {}   # lane -> logits of its chunk
        for j, r, take in single:
            piece = r.prompt_tokens[:take]
            tt = _i32([piece])
            pos = _i32([list(range(take))])
            if self.paged:
                logits, self.cache = self._prefill_paged_jit(
                    self.params, tt, pos, self._tables_for([r]),
                    _i32([r.slot]), self.cache, r.extras)
            else:
                slot = self.max_slots + j
                sub = cache_take(self.cache, slot, 1)
                logits, sub = self._prefill_jit(self.params, tt, pos, sub,
                                                r.extras)
                self.cache = cache_put(self.cache, sub, slot)
            rec.dispatched = True
            rec.payload["probe"] = logits
            last_logits[j] = logits[0]
        for take, entries in groups.items():
            if self.paged:
                # one paged graph per (rows, chunk) shape: the requests'
                # pinned state rows + block tables (DESIGN §9)
                reqs = [r for _, r, _ in entries]
                rows = _i32([r.slot for r in reqs])
                tt = _i32(
                    [r.prompt_tokens[r.prefill_pos:r.prefill_pos + take]
                     for r in reqs])
                pos = _i32(
                    [list(range(r.prefill_pos, r.prefill_pos + take))
                     for r in reqs])
                logits, self.cache = self._prefill_paged_jit(
                    self.params, tt, pos, self._tables_for(reqs), rows,
                    self.cache, None)
                rec.dispatched = True
                rec.payload["probe"] = logits
                for i, (j, _, _) in enumerate(entries):
                    last_logits[j] = logits[i]
                continue
            if len(entries) == 1:
                # single row: contiguous slice path (identical graph to the
                # legacy single-spare-row engine — keeps n_prefill_lanes=1
                # bit-for-bit)
                j, r, _ = entries[0]
                slot = self.max_slots + j
                piece = r.prompt_tokens[r.prefill_pos:r.prefill_pos + take]
                tt = _i32([piece])
                pos = _i32([list(range(r.prefill_pos,
                                       r.prefill_pos + take))])
                sub = cache_take(self.cache, slot, 1)
                logits, sub = self._prefill_jit(self.params, tt, pos, sub,
                                                None)
                self.cache = cache_put(self.cache, sub, slot)
                rec.dispatched = True
                rec.payload["probe"] = logits
                last_logits[j] = logits[0]
                continue
            rows = _i32([self.max_slots + j for j, _, _ in entries])
            tt = _i32(
                [r.prompt_tokens[r.prefill_pos:r.prefill_pos + take]
                 for _, r, _ in entries])
            pos = _i32(
                [list(range(r.prefill_pos, r.prefill_pos + take))
                 for _, r, _ in entries])
            logits, self.cache = self._prefill_lanes_jit(
                self.params, tt, pos, self.cache, rows)
            rec.dispatched = True
            rec.payload["probe"] = logits
            for i, (j, _, _) in enumerate(entries):
                last_logits[j] = logits[i]

        # deferred feed (DESIGN §14): lands when this interval retires
        rec.lane_tokens = {j: t for j, _, t in plan}
        self.prefill_tokens_trace.append(sum(t for _, _, t in plan))
        for _, r, take in plan:
            r.prefill_pos += take
            if self.prefix:
                # the chunk's K/V is in the pool: register its full blocks
                # in the prefix index (DESIGN §10)
                self.blocks.commit_prefill(r.rid, r.prompt_tokens,
                                           r.prefill_pos)
        # promote finished lanes (lane-index order: deterministic) into the
        # decode region: paged mode keeps the pinned row — an O(1)
        # bookkeeping move, zero tensor copies (DESIGN §9); contiguous mode
        # copies the lane row into the compacted region
        for j, r, take in sorted(plan, key=lambda e: e[0]):
            if r.prefill_pos < r.prompt_len:
                continue
            self.prefilling.remove(r)
            self.lanes[j] = None
            if not self.paged:
                dst = len(self.active)
                self._copy_row(dst, self.max_slots + j)
                r.slot = dst
            r.lane = -1
            r.state = RequestState.RUNNING
            # first token: the argmax stays on device; the TTFT stamp and
            # on_first_token feed land at retirement, when the token
            # actually exists (DESIGN §14) — queue_s is captured now so an
            # eviction between dispatch and retire can't corrupt the feed
            tok = jnp.argmax(last_logits[j][take - 1])
            flist = rec.payload.setdefault("first", [])
            rec.patches.append((r, len(r.output_tokens),
                                self._gen.get(r.rid, 0), "f", len(flist)))
            flist.append(tok)
            self._pending_tok[r.rid] = tok
            rec.firsts.append((r, self._gen.get(r.rid, 0), True,
                               r.prefill_start_time - r.arrival_time,
                               r.prefill_start_time))
            r.output_tokens.append(None)
            self.active.append(r)

    def run(self, max_steps: int = 100_000) -> int:
        steps = 0
        while self.step() and steps < max_steps:
            steps += 1
        return steps

    # -- internals ---------------------------------------------------------------
    def _prefill_request(self, r: Request, rec: _StepRec):
        # admission may have evicted cached blocks into this request's
        # table: their stale pos rows must be cleared before the first
        # attention read over the table (DESIGN §10)
        self._drain_released()
        if self.paged:
            slot = self._free_slots.pop()
            r.slot = slot
            self.cache = state_clear_row(self.cache, slot)
        else:
            slot = len(self.active)
            r.slot = slot
            self.cache = cache_clear_row(self.cache, slot)
        r.state = RequestState.PREFILLING
        r.prefill_start_time = self._now()
        chunk = self.prefill_chunk
        toks = r.prompt_tokens
        extras = getattr(r, "extras", None)
        last_logits = None
        # exact-size chunks: stateful families (SSM conv/recurrence) must not
        # see pad tokens — full chunks + one exact-size tail call (jit caches
        # one graph per distinct tail length). A shared prefix (DESIGN §10)
        # is already resident in mapped blocks: prefill the suffix only.
        start0 = r.cached_prefix_len if self.prefix else 0
        pieces = [(s, toks[s:s + chunk]) for s in range(start0, len(toks), chunk)]
        if self.paged:
            if self.prefix:
                self._cow_blocks(self.blocks.cow_range(r.rid, start0,
                                                       len(toks)))
            tables = self._tables_for([r])
            rows = _i32([slot])
            for start, piece in pieces:
                tt = _i32([piece])
                pos = _i32([list(range(start, start + len(piece)))])
                ex = extras if start == 0 else None
                logits, self.cache = self._prefill_paged_jit(
                    self.params, tt, pos, tables, rows, self.cache, ex)
                last_logits = logits[0, len(piece) - 1]
            if self.prefix:
                self.blocks.commit_prefill(r.rid, toks, len(toks))
        else:
            sub = cache_take(self.cache, slot, 1)
            for start, piece in pieces:
                tt = _i32([piece])
                pos = _i32([list(range(start, start + len(piece)))])
                ex = extras if start == 0 else None
                logits, sub = self._prefill_jit(self.params, tt, pos, sub, ex)
                last_logits = logits[0, len(piece) - 1]
            self.cache = cache_put(self.cache, sub, slot)
        r.state = RequestState.RUNNING
        if self.held:
            rec.held_rows.append((r, self._gen.get(r.rid, 0), slot))
        # first token deferred to retirement (DESIGN §14); the synchronous
        # path never fed on_first_token here (no chunked service split),
        # so only the TTFT stamp rides in rec.firsts
        tok = jnp.argmax(last_logits)
        flist = rec.payload.setdefault("first", [])
        rec.patches.append((r, len(r.output_tokens),
                            self._gen.get(r.rid, 0), "f", len(flist)))
        flist.append(tok)
        self._pending_tok[r.rid] = tok
        rec.firsts.append((r, self._gen.get(r.rid, 0), False, 0.0, 0.0))
        r.output_tokens.append(None)
        rec.dispatched = True
        rec.payload["probe"] = last_logits
        self.active.append(r)

    def _preempt_if_needed(self):
        if self.mem.bytes_per_token == 0:
            return  # constant per-request state: decode never grows it
        while self.active:
            need = sum(self.blocks.blocks_needed(r.context_len, 1, r.rid)
                       for r in self.active)
            if need <= self.blocks.free_blocks:
                return
            # newest victim first in BOTH modes (vLLM preemption order);
            # per victim, the DESIGN §11 crossover picks swap vs recompute
            victim = self.active[-1]
            if self._should_swap(victim):
                self._swap_out(len(self.active) - 1, victim)
            else:
                self._evict(len(self.active) - 1, victim)

    def _should_swap(self, r: Request) -> bool:
        """Per-victim preemption choice (DESIGN §11): swap only when the
        host pool can take the victim whole (shared ref>1 blocks are never
        swapped — the recompute path decrefs them instead) and the
        cost-model crossover says PCIe beats re-prefill. preempt="swap"
        forces swap whenever it is possible at all."""
        if not self.swap \
                or not self.blocks.can_swap_out(r.rid, self.max_blocks):
            return False
        if self.serve.preempt == "swap":
            return True
        return self.cost.swap_beats_recompute(
            len(self.blocks.tables[r.rid]), self.serve.block_size,
            r.context_len)

    def _swap_out(self, slot: int, r: Request):
        """Offload active[slot]'s KV blocks to the host pool: an O(blocks)
        `jax.device_get` of the victim's K/V/pos pool rows, then O(1)
        bookkeeping — its generated tokens and TTFT stand, it re-enters
        through the swapped queue without re-prefill (DESIGN §11)."""
        pairs = self.blocks.swap_out(r.rid)
        dev = _i32([d for d, _ in pairs])
        host = np.array([h for _, h in pairs], np.int32)
        for k, hp in self._host_pool.items():
            ax = 0 if k == "pos" else 1
            rows = jax.device_get(jnp.take(self.cache[k], dev, axis=ax))
            if k == "pos":
                hp[host] = rows
            else:
                hp[:, host] = rows
        # the device blocks are free now: clear their pos rows so a new
        # tenant never sees the swapped-out tenant's stale positions
        self._release_blocks([int(d) for d, _ in pairs])
        # model-level KV payload bytes — the SAME accounting the sim twin
        # and CostModel.pcie_s use, so the differential harness can assert
        # byte parity (the physical rows moved may be wider: pos map +
        # fp32 test pools)
        self.swap_out_bytes += self.mem.blocks_to_bytes(len(pairs))
        self.swap_outs += 1
        self.preemptions += 1
        r.state = RequestState.SWAPPED
        r.swap_out_time = self._now()
        if r.slot >= 0:
            self._free_slots.append(r.slot)
            r.slot = -1
        self.active.pop(slot)
        self.swapped.append(r)

    def _swap_in_next(self) -> bool:
        """Restore the oldest swapped request (FIFO) onto fresh device
        blocks, gated by the same watermark verdict as admission. Returns
        False when the pool cannot take it yet."""
        r = self.swapped[0]
        nb = len(self.blocks.swapped_tables[r.rid])
        if self.blocks.admission_verdict(nb, self.max_blocks) != "admit":
            return False
        pairs = self.blocks.swap_in(r.rid)
        # stale pos clears (cache evictions swap_in may have forced) land
        # BEFORE the restore, so they can never wipe the restored rows
        self._drain_released()
        host = np.array([h for h, _ in pairs], np.int32)
        dev = _i32([d for _, d in pairs])
        out = dict(self.cache)
        for k, hp in self._host_pool.items():
            if k == "pos":
                out[k] = out[k].at[dev].set(jnp.asarray(hp[host]))
            else:
                out[k] = out[k].at[:, dev].set(jnp.asarray(hp[:, host]))
        self.cache = out
        self.swap_in_bytes += self.mem.blocks_to_bytes(len(pairs))
        self.swap_ins += 1
        slot = self._free_slots.pop()
        r.slot = slot
        self.cache = state_clear_row(self.cache, slot)
        if r.swap_out_time >= 0:
            wait = self._now() - r.swap_out_time
            r.swapped_s += wait
            r.n_swaps += 1
            r.swap_out_time = -1.0
            self.swap_wait_trace.append(wait)
        r.state = RequestState.RUNNING
        self.swapped.pop(0)
        self.active.append(r)
        return True

    def _evict(self, slot: int, r: Request):
        """Evict active[slot] for recompute. `slot` is the index in
        `self.active`; paged mode just releases blocks + state row (O(1)),
        contiguous mode compacts by moving the last row into the hole."""
        self._free_request(r)
        r.state = RequestState.WAITING
        # new life generation (DESIGN §14): in-flight patches recorded
        # against the cleared outputs must not land on the recompute pass
        self._gen[r.rid] = self._gen.get(r.rid, 0) + 1
        r.output_tokens.clear()
        # the recompute pass re-probes the prefix index from scratch — the
        # request's own just-freed blocks are prime cache hits (DESIGN §10)
        r.cached_prefix_len = 0
        # recompute: the next serving pass re-attributes TTFT from scratch
        # (a stale prefill_start_time would count the first life — decode
        # included — as prefill service); the lifecycle stamps describe
        # the last life only (DESIGN §16)
        r.admit_time = r.prefill_start_time = r.first_token_time = -1.0
        if self.paged:
            self.active.pop(slot)
        else:
            last = len(self.active) - 1
            if slot != last:
                self._copy_row(slot, last)
                self.active[slot] = self.active[last]
                self.active[slot].slot = slot
            self.active.pop()
        self.waiting.insert(0, r)
        self.preemptions += 1

    def _decode_once(self, rec: _StepRec):
        n = len(self.active)
        ge = [b for b in self.buckets if b >= n]
        bucket = min(ge) if ge else self.max_slots
        with _span(SPAN_DECODE, **self._span_args(rows=n, bucket=bucket)):
            self._decode_batch(rec, n, bucket)

    def _decode_batch(self, rec: _StepRec, n: int, bucket: int):
        """Dispatch one decode step over the `n` active rows padded to
        `bucket`, then grow, finish and preempt by the step's lengths."""
        if self.prefix:
            # COW guard on the position each decode writes (DESIGN §10)
            for r in self.active:
                self._cow_blocks(self.blocks.cow_range(
                    r.rid, r.context_len - 1, r.context_len))
        # inputs: retired tokens are host ints; un-retired ones (pipeline
        # depth >= 1, or promoted this very interval) are still device
        # scalars and are spliced in without a readback — the VALUES are
        # identical to the synchronous loop's, so the decode graph sees
        # the same inputs bit for bit (DESIGN §14)
        toks: List[int] = []
        pend: List[Tuple[int, Any]] = []
        for i, r in enumerate(self.active):
            v = r.output_tokens[-1]
            if v is None:
                toks.append(0)
                pend.append((i, self._pending_tok[r.rid]))
            else:
                toks.append(v)
        toks += [0] * (bucket - n)
        # the pending token sits at absolute position context_len - 1
        lens = [r.context_len - 1 for r in self.active] + [-1] * (bucket - n)
        tt = _i32(toks)
        for i, dv in pend:
            tt = tt.at[i].set(dv)
        ll = _i32(lens)

        if self.paged:
            rows = _i32([r.slot for r in self.active]
                        + [self.n_slots] * (bucket - n))
            tables = self._tables_for(self.active, pad_to=bucket,
                                      kind="decode")
            logits, self.cache = self._decode_paged_jit(
                self.params, tt, ll, tables, rows, self.cache)
        else:
            sub = cache_take(self.cache, 0, bucket)
            logits, sub = self._decode_jit(self.params, tt, ll, sub)
            self.cache = cache_put(self.cache, sub, 0)

        # the key split is host-side and dispatch-ordered, so sampling
        # stays bit-identical to the synchronous loop at every depth
        self.key, sk = jax.random.split(self.key)
        rec.payload["dec"] = sample(logits[:n], sk, self.temperature)
        rec.n_decode = n
        rec.dispatched = True
        self.batch_trace.append(n)
        self.decode_steps += 1
        self.total_decoded += n

        if self.held:
            rec.held_rows.extend((r, self._gen.get(r.rid, 0), r.slot)
                                 for r in self.active)
        sampled = rec.payload["dec"]
        finished = []
        grow_failed = []
        for i, r in enumerate(self.active):
            # grow the KV footprint for the NEXT step's write. State-only
            # families (bytes_per_token == 0) hold constant per-request
            # state — growing them would drain free_tokens linearly and
            # starve admission with phantom usage.
            grew = True
            if self.mem.bytes_per_token != 0:
                grew = self.blocks.allocate(r.rid, r.context_len, 1)
            # value-independent bookkeeping (DESIGN §14): the token's
            # VALUE is still in flight, but its existence — length growth,
            # finish at max_new_tokens/max_context — is not. Append a
            # placeholder now, patch it at retirement.
            rec.patches.append((r, len(r.output_tokens),
                                self._gen.get(r.rid, 0), "d", i))
            r.output_tokens.append(None)
            self._pending_tok[r.rid] = sampled[i]
            if len(r.output_tokens) >= r.max_new_tokens \
                    or r.context_len >= self.max_context - 1:
                finished.append(i)
            elif not grew:
                # failed grow: the emitted token has no backing block for
                # its successor — preempt (recompute) instead of silently
                # drifting the allocator
                grow_failed.append(r)
        for i in sorted(finished, reverse=True):
            r = self.active[i]
            r.state = RequestState.FINISHED
            rec.completions.append((r, len(r.output_tokens)))
            self._free_request(r)
            if self.paged:
                self.active.pop(i)
            else:
                last = len(self.active) - 1
                if i != last:
                    self._copy_row(i, last)
                    self.active[i] = self.active[last]
                    self.active[i].slot = i
                self.active.pop()
            self.total_finished += 1
        for r in grow_failed:
            if r in self.active:
                self._evict(self.active.index(r), r)
        # decode grows may have reclaimed cached blocks for reuse
        self._drain_released()

    def _retire_step(self) -> float:
        """Retire the oldest in-flight interval (DESIGN §14): fence on its
        device futures — the timed wait IS the interval's device time,
        the latency the host could not hide — then pull the sampled and
        first tokens in ONE batched transfer, patch their output-token
        placeholders, stamp TTFT/TBT at retirement (timestamps mark
        result availability, not dispatch), apply the interval's deferred
        telemetry feeds, and seal the allocator's shadow epoch. Returns
        the fence wait in seconds."""
        rec = self._inflight.popleft()
        t0 = time.perf_counter()
        with _span(SPAN_FENCE):
            # THE pipeline fence: the one block the async loop retains
            jax.block_until_ready(rec.payload)
        dev_s = time.perf_counter() - t0
        with _span(SPAN_READBACK):
            # everything is ready — one bulk readback, not per-token syncs
            vals = jax.device_get(rec.payload)
        with _span(SPAN_STAMP):
            self._stamp_retired(rec, vals, dev_s)
        return dev_s

    def _stamp_retired(self, rec: _StepRec, vals: Dict[str, Any],
                       dev_s: float) -> None:
        """Patch a retired interval's tokens, stamp its TTFT/finish times,
        apply its deferred telemetry feeds and commit the shadow epoch."""
        dt_ms = dev_s * 1e3
        now = self._now()
        self._last_retire = now
        dec = vals.get("dec")
        first = vals.get("first", ())
        for r, idx, gen, kind, k in rec.patches:
            if self._gen.get(r.rid, 0) != gen:
                continue   # evicted since dispatch: that life's outputs
                           # were cleared; recompute re-emits them
            if idx < len(r.output_tokens) and r.output_tokens[idx] is None:
                r.output_tokens[idx] = int(dec[k] if kind == "d"
                                           else first[k])
        held = vals.get("held")
        for r, gen, slot in rec.held_rows:
            if self._gen.get(r.rid, 0) == gen:
                counts = held[0, slot].tolist()
                self.held_selections_total += \
                    sum(counts) - sum(r.held_selections)
                r.held_selections = counts
        if rec.lane_tokens is not None:
            self.tel.on_prefill_interval(rec.lane_tokens, self.n_lanes)
        for r, gen, feed, queue_s, t_ps in rec.firsts:
            # a life evicted since dispatch gets no stamp: its
            # recompute pass stamps anew
            if self._gen.get(r.rid, 0) == gen:
                r.first_token_time = now
            self.ttft_trace.append(now - r.arrival_time)
            if feed:
                self.tel.on_first_token(queue_s, now - t_ps)
        if rec.n_decode:
            self.tel.on_decode_step(dt_ms, rec.n_decode)
            self.tbt_trace.append(dt_ms)
            self._sla_steps += 1
            if self.serve.d_sla_ms <= 0 or dt_ms <= self.serve.d_sla_ms \
                    + self.serve.eps_d_ms:
                self._sla_ok += 1
        for r, n_out in rec.completions:
            r.finish_time = now
            # goodput verdict (DESIGN §15): stamped at retirement — the
            # firsts loop above has already finalized first_token_time
            if r.stamp_sla(self.serve.ttft_sla_s, self.serve.tbt_sla_ms):
                self.sla_requests_met += 1
                self.goodput_tokens += n_out
            self.tel.on_completion(n_out)
        # seal the shadow epoch: blocks freed since the last retirement
        # are safe for arbitrary reuse now that the step that could still
        # read them has been fenced; open the next epoch for the frees
        # the remaining in-flight interval(s) will record
        self.blocks.shadow_commit()
        self.blocks.shadow_begin()

    # -- metrics ---------------------------------------------------------------
    def summary(self) -> Dict[str, float]:
        # rates over the serving window: the first submitted request's
        # arrival to the last retirement (0 before any retirement)
        el = 0.0
        if self._first_arrival is not None and self._last_retire is not None:
            el = max(self._last_retire - self._first_arrival, 0.0)
        occ = self.tel.lane_occ
        tq, _ = self.tel.ttft_queue.get()
        tp, _ = self.tel.ttft_prefill.get()
        tbts = sorted(self.tbt_trace)
        ttfts = sorted(self.ttft_trace)
        return {
            "throughput_tok_s": self.total_decoded / max(el, 1e-9),
            "total_tokens": float(self.total_decoded),
            "duration_s": el,
            # mesh-sharded serving (DESIGN §12): effective model-axis
            # shards of the KV pool and the resulting token capacity
            "model_shards": float(self.model_shards),
            "pool_tokens": float(self.mem.eta),
            "decode_steps": self.decode_steps,
            "mean_batch": (sum(self.batch_trace) / len(self.batch_trace))
            if self.batch_trace else 0.0,
            "tbt_ms_mean": (sum(self.tbt_trace) / len(self.tbt_trace))
            if self.tbt_trace else 0.0,
            "tbt_ms_p95": tbts[int(0.95 * (len(tbts) - 1))] if tbts else 0.0,
            "sla_attainment": (self._sla_ok / self._sla_steps)
            if self._sla_steps else 0.0,
            # per-request goodput SLOs (DESIGN §15): throughput counting
            # only SLA-met requests' tokens
            "goodput_tok_s": self.goodput_tokens / max(el, 1e-9),
            "goodput_tokens": float(self.goodput_tokens),
            "sla_requests_met": self.sla_requests_met,
            "request_sla_attainment": self.sla_requests_met
            / max(self.total_finished + self.rejected, 1),
            # host-vs-device interval split (DESIGN §14)
            "step_host_s_mean": (sum(self.step_host_trace)
                                 / len(self.step_host_trace))
            if self.step_host_trace else 0.0,
            "step_device_s_mean": (sum(self.step_device_trace)
                                   / len(self.step_device_trace))
            if self.step_device_trace else 0.0,
            "finished": self.total_finished,
            "admitted": self.admitted_total,
            "preemptions": self.preemptions,
            "oom_events": self.oom_events,
            "rejected": self.rejected,
            # two-tier swap (DESIGN §11)
            "swap_outs": self.swap_outs,
            "swap_ins": self.swap_ins,
            "swap_out_bytes": float(self.swap_out_bytes),
            "swap_in_bytes": float(self.swap_in_bytes),
            "swapped_peak": float(self.blocks.swapped_peak),
            "swap_latency_s_mean": (sum(self.swap_wait_trace)
                                    / len(self.swap_wait_trace))
            if self.swap_wait_trace else 0.0,
            # contiguous-layout row copies; 0 under paged_kv (DESIGN §9)
            "copy_rows": float(self.copy_rows),
            "copy_bytes": float(self.copy_bytes),
            # prefix sharing (DESIGN §10)
            "prefix_hit_rate": self.blocks.prefix_hit_rate,
            "prefix_hit_tokens": float(self.blocks.prefix_hit_tokens),
            "prefix_query_tokens": float(self.blocks.prefix_query_tokens),
            "cached_blocks": float(self.blocks.cached_blocks),
            "cache_evictions": float(self.blocks.cache_evictions),
            "logical_used_tokens": float(self.blocks.logical_used_tokens),
            "physical_used_tokens": float(self.blocks.physical_used_tokens),
            "logical_used_bytes": float(self.mem.tokens_to_bytes(
                self.blocks.logical_used_tokens)),
            "physical_used_bytes": float(self.mem.tokens_to_bytes(
                self.blocks.physical_used_tokens)),
            # PD fusion (DESIGN §6)
            "prefill_lane_occupancy": (sum(occ) / len(occ)) if occ else 0.0,
            "prefill_tokens": float(self.tel.prefill_tokens_total),
            "ttft_queue_s_mean": tq,
            "ttft_prefill_s_mean": tp,
            "ttft_mean_s": (sum(ttfts) / len(ttfts)) if ttfts else 0.0,
            "ttft_p90_s": ttfts[int(0.9 * (len(ttfts) - 1))]
            if ttfts else 0.0,
        }
