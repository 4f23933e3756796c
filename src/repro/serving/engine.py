"""Batched, host-sync-free serving engine (continuous batching) over
(compressed) weights, with a paged (block-table) KV cache for attention
models and a dense (max_batch, max_len) slab fallback for everything else.

Slot-based: requests are admitted into free slots, every engine step decodes
one token for all live rows, finished rows free their slot — and their KV
blocks — immediately, so new requests join mid-flight without stalling the
running batch.

Hot-path design (the paper's Eq. 6 payoff is only real if the engine's
memory path keeps up with the factored matmuls):

  * ALL per-slot state lives on device: cache, cache_len, last_token,
    active flags and a per-slot PRNG key array.  The host mirrors only what
    it needs for scheduling, updated from host-side bookkeeping plus the one
    token vector each step already transfers — never by extra syncs.
  * Every jit root DONATES its cache/state buffers (``donate_argnums``), so
    the multi-MB cache is aliased in place by XLA instead of being copied
    every step.
  * ``step()`` is ONE jitted call (decode + batched sampling + device-side
    finish exits for every live row) followed by AT MOST one device->host
    transfer of a sampled token vector.  A row that samples its eos id,
    spends its last budgeted token, or hits the max_len bound clears its
    own active flag on device; the host learns from the tokens it already
    has.
  * The step loop is PIPELINED (``pipeline_depth``, default 2): because
    every finish reason is device-authoritative, step N+1's decode root can
    be dispatched before step N's token transfer is consumed — the engine
    keeps a small ring of in-flight token futures and syncs only the oldest
    when the ring is full, so token emission, slot/block freeing and
    request admission bookkeeping overlap the device's next step instead
    of serializing behind a host round-trip every token.  Depth 1 is
    bit-for-bit the unpipelined engine, and any depth produces identical
    token streams (the device state chain never observes the host's lag).
    Host-mutating events that need a synced view — admission, defrag,
    dynamic-k speculation — drain the ring first (``drain()``).

Paged path (``models.api.cache_layout(model) == "paged"``: pure-attention
stacks — see serving/kvcache/):

  * K/V live in a shared block pool (num_blocks, block_size, ...) instead of
    a dense slab, addressed through per-slot block-table rows, so cache HBM
    scales with pool capacity (live tokens), not max_batch * max_len.
  * Scheduling is a separate policy module (serving/scheduler): by default
    admission reserves only the PROMPT's blocks and the reservation grows
    at block boundaries as the row decodes (allocate-on-demand, so pool
    occupancy tracks live tokens and more rows fit a fixed pool), with
    victim preemption — most-blocks row evicted, resumed by re-prefill or
    host swap-back — when growth or a higher-priority admission runs a
    shard dry.  SLA latency classes queue separately with starvation-free
    aging, and DP placement targets the emptiest shard's sub-pool.
    ``SchedulerConfig(admission="worst_case")`` restores the PR-3 contract
    (prompt + max_new reserved up front; exhaustion surfaces only as
    admission backpressure, never mid-decode).
  * Prefill is CHUNKED: prompts stream into their blocks ``prefill_chunk``
    tokens per engine iteration through one fixed-shape jit root (compiles
    exactly once), interleaved with decode steps so a very long prompt
    cannot stall the running batch.
  * Decode attends through ``kernels/paged_attention`` (Pallas kernel
    streaming exactly the live pages on TPU, jnp gather oracle elsewhere),
    honoring the int8 KV quantization of the dense path.

Dense path (recurrent SSM/RWKV state, token-choice MoE, MLA latents,
enc-dec): the PR-1 design — bucketed batched prefill-admission (pad-safe
models compile once per power-of-two prompt-length bucket; pad-sensitive
ones fall back to exact-length prefill) — now with donated jit roots and the
same device-side EOS exit.

Decode-time nested-lowrank matmuls of compressed layers (dense, attention,
MLP, and the stacked MoE expert FFNs) route through
``kernels/nested_lowrank/ops.py`` (fused Pallas kernel on TPU for
decode-shaped rows, jnp oracle on CPU).

Speculative decoding (``spec_config``, serving/spec/): a higher-compression
NSVD twin of the weights drafts ``k`` tokens per step in one fused jit root
(K+1 sequential cheap decodes over the draft's own paged/dense cache); the
target verifies the whole proposal matrix through the same S>1 chunk-decode
path chunked prefill uses and commits the accepted prefix plus one
correction/bonus token via on-device accept/resample — greedy is
token-identical to non-speculative decode, temperature>0 preserves the
target distribution exactly.  Both caches' per-row lengths roll back to the
committed prefix on device; a step is still exactly two jitted calls and
ONE D2H transfer (the packed committed-token matrix).

Mesh sharding (``parallelism=`` over a launch/mesh.make_serving_mesh DP x TP
mesh): every jit root runs SPMD with explicit in/out NamedShardings
(launch/steps.ServingShardings) — weights TP-sharded via the existing
param_pspecs (factored NSVD layers all-reduce rank-k partials, not
d_model), per-slot state and host-built (B, ...) inputs data-parallel over
slots, the dense slab sharded over its batch dim and the paged block pools
over their block dim with PER-SHARD block id ranges: slot s maps to DP
shard s*dp/max_batch, its reservations come from that shard's range, and
admission/free/defrag/rollback stay host-authoritative per shard
(serving/kvcache).  The donation and one-D2H-per-step contracts are
unchanged — sampled tokens leave via ONE sharded transfer, and a (1, 1)
mesh reproduces the meshless single-device engine bit-for-bit (pinned by
tests/test_sharded_serving.py).  When max_batch does not divide the DP
size, slot/pool sharding falls back to replicated (weights stay TP).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
import time
import zlib
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.steps import (
    POISON_TOKEN,
    RootContext,
    ServingShardings,
    named,
    serving_root_registry,
)
from repro.models.api import (
    Model,
    build_model,
    cache_layout,
    prefill_pad_safe,
    serving_cache_pspecs,
)
from repro.obs import NULL_TELEMETRY
from repro.parallel.sharding import Parallelism
from repro.runtime.straggler import StepTimeWatchdog
from repro.serving.faults import (
    FaultPlan,
    FaultPolicy,
    ServingFault,
    ServingFaultHandler,
)
from repro.serving.kvcache import PagedKVCache
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.serving.spec import DraftState, SpecConfig


def _swap_checksum(blocks) -> int:
    """CRC32 chained over a swap payload's host leaves (flatten order is
    deterministic for a fixed pool pytree), so a corrupted copy is caught
    at resume instead of scattering garbage KV back onto the device."""
    crc = 0
    for leaf in jax.tree.leaves(blocks):
        crc = zlib.crc32(np.ascontiguousarray(leaf).tobytes(), crc)
    return crc


@dataclasses.dataclass
class _SwapPayload:
    """A preempted row's KV prefix, swapped to host for a copy-back resume:
    the block rows covering its committed context, plus the row's PRNG key
    so the sampling chain continues where it stopped (temperature streams
    stay identical to an un-preempted run)."""
    n_ctx: int            # committed context length the blocks cover
    n_blocks: int         # leading block count of every ``blocks`` leaf
    blocks: object        # host pytree of per-layer pool block rows
    key_row: np.ndarray   # (2,) uint32 saved sampling-key state
    # CRC32 over the leaves at swap-out time; a mismatch at resume means
    # the host copy was corrupted and the engine falls back to reprefill.
    checksum: Optional[int] = None

    @property
    def nbytes(self) -> int:
        import jax as _jax
        return int(sum(leaf.nbytes for leaf in _jax.tree.leaves(self.blocks)))


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    # SLA admission (serving/scheduler): latency class name + queue index.
    latency_class: Optional[str] = None
    class_idx: int = 0
    # Preemption bookkeeping: eviction count, and the host-swapped KV
    # payload when the scheduler resumes by copy-back instead of re-prefill
    # (reprefill resumes instead fold ``generated`` into ``prompt``;
    # ``prompt_absorbed`` counts how many generated tokens the prompt
    # already holds, so a SECOND preemption folds only the new suffix).
    preemptions: int = 0
    prompt_absorbed: int = 0
    swap: Optional[_SwapPayload] = None
    # Fault tolerance (serving/faults): absolute deadline (time.monotonic)
    # for admission-side shedding, the terminal reason (one of
    # faults.FINISH_REASONS; None until the request finishes), and how
    # many poison-quarantine retries this request has burned.
    deadline: Optional[float] = None
    finish_reason: Optional[str] = None
    retries: int = 0
    # Speculative-decoding accounting (spec_config engines only).
    spec_proposed: int = 0
    spec_accepted: int = 0
    # Lifecycle timestamps (time.perf_counter; populated only when the
    # engine runs with telemetry enabled — see repro.obs).
    t_submit: float = 0.0
    t_first: float = 0.0
    t_last: float = 0.0

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens

    @property
    def prefix_len(self) -> int:
        """Tokens admission must cover: the prompt (re-prefill resumes
        fold generated tokens into it) or the swapped context length."""
        return self.swap.n_ctx if self.swap is not None else len(self.prompt)

    @property
    def acceptance_rate(self) -> float:
        return self.spec_accepted / max(1, self.spec_proposed)


@dataclasses.dataclass
class _PrefillTask:
    """A request streaming its prompt into reserved blocks, chunk by chunk."""
    req: Request
    slot: int
    pos: int = 0  # next prompt position to feed


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unconsumed decode step in the pipeline ring.

    ``tokens`` is the step's device-resident result (the sampled token
    vector, or the packed [tokens|n_commit|m] matrix in speculative mode);
    ``mask`` snapshots the host's active view at dispatch so consumption
    attributes tokens to the rows that were live then.  FIFO consumption
    keeps the invariant that a row live on the host at consume time was
    device-active at this entry's dispatch (every device exit has a host
    twin that fires when the triggering entry is consumed — earlier in the
    ring by construction)."""
    tokens: jax.Array
    mask: np.ndarray
    dispatch_s: float
    spec: bool = False
    k_row: Optional[np.ndarray] = None


_PIPELINE_DEPTH_ENV = "REPRO_SERVING_PIPELINE_DEPTH"
_TRANSFER_GUARD_ENV = "REPRO_SERVING_TRANSFER_GUARD"


class ServingEngine:
    def __init__(
        self,
        model: Model,
        params,
        max_batch: int = 8,
        max_len: int = 512,
        seed: int = 0,
        bucket_min: int = 16,
        paged: Optional[bool] = None,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        prefill_chunk: int = 64,
        eos_id: Optional[int] = None,
        kv_quant: bool = False,
        spec_config: Optional[SpecConfig] = None,
        parallelism: Optional[Parallelism] = None,
        pipeline_depth: Optional[int] = None,
        transfer_guard: Optional[bool] = None,
        telemetry=None,
        sched_config: Optional[SchedulerConfig] = None,
        faults: Optional[FaultPlan] = None,
        fault_policy: Optional[FaultPolicy] = None,
    ):
        # Observability (repro.obs.Telemetry, or the shared no-op).  All
        # hooks consume host bookkeeping + the packed D2H word the step
        # already transfers — never an extra device sync — and per-row
        # work is gated on ``self.obs.enabled`` so the default path stays
        # no-op (pinned by tests/test_observability.py).
        self.obs = telemetry if telemetry is not None else NULL_TELEMETRY
        self._obs_blocked: set = set()
        if self.obs.enabled and spec_config is not None:
            self.obs.spec_meta.setdefault("k", spec_config.k)
            if spec_config.draft_ratio is not None:
                self.obs.spec_meta.setdefault("draft_ratio",
                                              spec_config.draft_ratio)
        if pipeline_depth is None:
            pipeline_depth = int(os.environ.get(_PIPELINE_DEPTH_ENV, "2"))
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        self.pipeline_depth = pipeline_depth
        if transfer_guard is None:
            transfer_guard = os.environ.get(
                _TRANSFER_GUARD_ENV, "0").lower() not in ("", "0", "false")
        self.transfer_guard = bool(transfer_guard)
        par = (parallelism
               if parallelism is not None and parallelism.active else None)
        self.par = par
        if par is not None:
            # Rebuild the model facade against the mesh so its internal
            # activation constraints (batch DP, logits TP) apply inside
            # every root; params/caches are plain pytrees, so the rebuilt
            # facade is interchangeable with the caller's.
            model = build_model(model.cfg, par)
            dp_size = par.dp_size
            # Slots (and with them the paged pools' block ranges) shard
            # over DP only when they divide it; otherwise per-slot state
            # and the cache stay replicated while weights keep TP.
            self.dp_shards = dp_size if max_batch % dp_size == 0 else 1
        else:
            self.dp_shards = 1
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id

        layout = cache_layout(model)
        self.paged = (layout == "paged") if paged is None else bool(paged)
        if self.paged and layout != "paged":
            raise ValueError(
                f"model {model.cfg.name!r} has cache layout {layout!r}; "
                "paging requires a pure-attention cache (models.api.cache_layout)"
            )
        self.spec = spec_config
        if self.spec is not None and layout != "paged":
            raise ValueError(
                f"model {model.cfg.name!r} has cache layout {layout!r}; "
                "speculative decoding needs pure-attention caches (chunk "
                "verification and length rollback have no recurrent/MoE/MLA "
                "form)"
            )

        # Scheduling policy (serving/scheduler): per-class admission
        # queues, on-demand vs worst-case block reservation, preemption
        # + resume mode, DP placement, and decode-row dispatch order.
        self.sched = Scheduler(sched_config)
        if (self.sched.resume_mode == "swap" and spec_config is not None):
            raise ValueError(
                "resume='swap' is unsupported with speculative decoding "
                "(the draft pool's swapped prefix has no catch-up path); "
                "use resume='reprefill'"
            )

        # Device-resident state (never read back except the sampled tokens).
        self.cache_len = jnp.zeros((max_batch,), jnp.int32)
        self.last_token = jnp.zeros((max_batch,), jnp.int32)
        self.budget_dev = jnp.zeros((max_batch,), jnp.int32)
        self.key_data = jax.random.key_data(
            jax.random.split(jax.random.key(seed), max_batch)
        )
        # Per-request key derivation (see Request.key_data).
        self._base_key = jax.random.key(seed)
        self._draft_base_key = (jax.random.key(spec_config.seed)
                                if spec_config is not None else None)
        self._active_dev = jnp.zeros((max_batch,), bool)

        # Host mirrors for scheduling (updated by bookkeeping + the step's
        # own token transfer, not extra syncs).
        self.active = np.zeros((max_batch,), bool)
        self.temps = np.zeros((max_batch,), np.float32)
        self._eos = np.full((max_batch,), -1, np.int32)
        self._len_host = np.zeros((max_batch,), np.int64)
        # On-demand growth bookkeeping: ``_dev_len`` conservatively mirrors
        # each row's DEVICE cache length at dispatch time (host ``_len_host``
        # lags by the pipeline ring), so coverage targets never undershoot
        # a write the device is about to make.  ``_stalled`` rows are live
        # but frozen (host_keep=False) because their shard ran dry with
        # preemption disabled — they resume exactly where they froze once
        # blocks free up.
        self._dev_len = np.zeros((max_batch,), np.int64)
        self._stalled = np.zeros((max_batch,), bool)
        # Scheduler lifecycle counters + occupancy accumulators (plain
        # host ints/floats; surfaced by scheduler_stats() and the bench).
        self.sched_events: Dict[str, int] = {
            "preemptions": 0, "swap_bytes": 0, "grown_blocks": 0,
            "resumes": 0, "stalls": 0,
        }
        self._occ_live_frac_sum = 0.0
        self._occ_samples = 0
        self._occ_rows_sum = 0
        self._occ_rows_steps = 0

        # Fault injection + degradation (serving/faults).  The plan is a
        # pure chaos surface consumed at explicit injection sites —
        # without one, every site is a single ``is None`` check.  The
        # policy/handler own quarantine-vs-retry dispositions; the
        # watchdog (built only when chaos/policy is requested) classifies
        # per-step durations and enforces the hard step timeout.
        self._faults = faults
        self._fault_policy = (fault_policy if fault_policy is not None
                              else FaultPolicy())
        self._handler = ServingFaultHandler(self._fault_policy)
        self._watchdog = (StepTimeWatchdog(self._fault_policy.straggler)
                          if faults is not None or fault_policy is not None
                          else None)
        # Chaos-variant roots (a trailing poison input on the steady
        # sampling roots) are built only when the plan can poison logits;
        # otherwise the roots are byte-identical to a fault-free engine's.
        self._chaos = faults is not None and faults.has("poison_logits")
        self._poison_zero = None
        self._step_idx = 0  # monotonic dispatch counter (decode/spec)
        # Backoff-parked poison retries: (ready_step, Request).
        self._parked: List[Tuple[int, Request]] = []
        self._has_deadlines = False
        self._draining = False
        self._closed = False
        self._draft_dead = False
        self._draft_off_until = 0
        # uid -> Request for every terminal exit (normal or aborted), so
        # finish_reason accounting can never miss a path.
        self.finished_requests: Dict[int, Request] = {}
        self.fault_events: Dict[str, int] = {
            "quarantined": 0, "retried": 0, "shed": 0, "cancelled": 0,
            "swap_fallbacks": 0, "draft_kills": 0, "draft_reenables": 0,
            "straggler_slow": 0, "straggler_trips": 0,
        }

        # Device-resident copies of the loop-invariant host inputs
        # (host_keep / temps / eos [/ k_row]).  They only change on slot
        # (re)admission or a finish, so dispatch reuses the cached arrays
        # instead of re-uploading three (B,) host arrays every step; any
        # bookkeeping that mutates them flips ``_host_dirty``.
        self._host_dirty = True
        self._keep_dev = None
        self._temps_dev = None
        self._eos_dev = None
        self._k_row_dev = None
        self._order_dev = None

        # Pipeline ring of dispatched-but-unconsumed steps, plus finished
        # requests produced by internal drains (handed out by the next
        # public step()/_admit()/drain()).
        self._ring: deque[_InFlight] = deque()
        self._pending_finished: List[Request] = []

        self.slots: List[Optional[Request]] = [None] * max_batch
        self._prefilling: List[_PrefillTask] = []
        self._uid = itertools.count()
        # Free slots are handed out in the order they FREED, not by index.
        # Token streams never depend on slot choice (sampling keys are
        # per-REQUEST, see Request.key_data), but freed-order assignment
        # keeps slot/pool layouts closer across pipeline depths — at depth
        # 2 two finishes can surface from one drain, and an index-ordered
        # free list would swap their successors' slots relative to depth 1.
        self._free_clock = itertools.count()
        self._freed_at = np.arange(max_batch, dtype=np.int64) - max_batch
        self._bucketed = prefill_pad_safe(model)

        if self.paged:
            self.kv = PagedKVCache(
                model, max_batch, max_len, block_size=block_size,
                num_blocks=num_blocks, kv_quant=kv_quant,
                dp_shards=self.dp_shards, par=par,
            )
            self.prefill_chunk = prefill_chunk
            self._sh = (ServingShardings(par, params, self.kv.shardings,
                                         max_batch)
                        if par is not None else None)
            if par is not None:
                self.params = params = jax.device_put(params,
                                                      self._sh.params)
                # Cached block-table mirror must be born with the roots'
                # expected (B, M) sharding (see PagedKVCache.table_device).
                self.kv.table_sharding = self._sh.mat
        else:
            self.cache = model.init_cache(max_batch, max_len,
                                          kv_quant=kv_quant)
            self._sh = None
            if par is not None:
                cache_sh = named(
                    serving_cache_pspecs(model, par, max_batch=max_batch,
                                         max_len=max_len,
                                         kv_quant=kv_quant,
                                         shapes=self.cache),
                    par.mesh,
                )
                self._sh = ServingShardings(par, params, cache_sh,
                                            max_batch)
                self.params = params = jax.device_put(params,
                                                      self._sh.params)
                self.cache = jax.device_put(self.cache, cache_sh)
            self._buckets = self._make_buckets(bucket_min, max_len)

        # All jit roots come from the serving root registry (the same specs
        # the static auditor traces): builder, donate_argnums and sharding
        # hook live in ONE place, so an audited contract is by construction
        # the contract the engine runs.
        self._ctx = RootContext(
            model=model, max_batch=max_batch, max_len=max_len,
            kv_quant=kv_quant, prefill_chunk=prefill_chunk,
            block_size=block_size,
            num_blocks=self.kv.num_blocks if self.paged else None,
            spec_k=spec_config.k if spec_config is not None else 4,
            bucketed=self._bucketed, dp_shards=self.dp_shards,
            chaos=self._chaos,
        )
        self._roots = {r.name: r for r in serving_root_registry(
            "paged" if self.paged else "dense",
            spec=spec_config is not None)}
        if self.paged:
            self._decode = self._root("paged_decode")
            self._chunk_step = self._root("paged_prefill_chunk")
        else:
            self._decode = self._root("decode")
            self._prefill = self._root("prefill_admit")

        if self._sh is not None:
            # Per-slot device state lives sharded from birth so the roots'
            # donated buffers alias in place (resharding would copy).
            self.cache_len = jax.device_put(self.cache_len, self._sh.row)
            self.last_token = jax.device_put(self.last_token, self._sh.row)
            self.budget_dev = jax.device_put(self.budget_dev, self._sh.row)
            self.key_data = jax.device_put(self.key_data, self._sh.mat)
            self._active_dev = jax.device_put(self._active_dev,
                                              self._sh.row)

        if self.spec is not None:
            draft_params = self.spec.draft_params
            dparams_sh = None
            if self._sh is not None:
                # Draft weights follow the same TP rules (factored leaves
                # shard by the u/v orientation rules); the draft cache
                # inherits the target's shardings by construction.
                dparams_sh = self._sh.tree(draft_params)
                draft_params = jax.device_put(draft_params, dparams_sh)
            self.draft = DraftState(
                model, draft_params, max_batch, max_len,
                paged=self.paged, block_size=block_size,
                num_blocks=num_blocks, kv_quant=kv_quant,
                seed=self.spec.seed, dp_shards=self.dp_shards, par=par,
                cache_shardings=(None if self.paged or self._sh is None
                                 else self._sh.cache),
                key_sharding=self._sh.mat if self._sh else None,
            )
            if self.paged and self._sh is not None:
                self.draft.kv.table_sharding = self._sh.mat
            self._spec_draft = self._root("spec_draft", dparams_sh)
            self._spec_verify = self._root("spec_verify", dparams_sh)
            self._draft_prefill = self._root("draft_prefill", dparams_sh)
            # Per-row speculation windows (all k unless dynamic_k shrinks).
            self._k_row = np.full((max_batch,), self.spec.k, np.int32)
            self.spec_proposed = 0
            self.spec_accepted = 0
            self.spec_committed = 0
            self.spec_step_rows = 0
        else:
            self.draft = None

        # Telemetry: per-consumed-step wall times (dispatch + D2H sync +
        # host bookkeeping) plus the sync/host breakdown the benchmark
        # reports (device wait vs host-side work per step).
        self.step_times: List[float] = []
        self.step_device_wait_s: List[float] = []
        self.step_host_s: List[float] = []
        self.decode_transfers = 0

    @staticmethod
    def _jit(fn, donate, shardings=None):
        """jit a serving root: donation always; explicit in/out shardings
        when the engine runs on a mesh (pinning donated-buffer aliasing and
        step-to-step layout stability)."""
        if shardings is None:
            return jax.jit(fn, donate_argnums=donate)
        in_sh, out_sh = shardings
        return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate)

    def _root(self, name: str, draft_params_sh=None):
        """Build one jitted serving root from its registry spec."""
        spec = self._roots[name]
        sh = (spec.shardings(self._sh, self._ctx, draft_params_sh)
              if self._sh is not None else None)
        return self._jit(spec.build(self._ctx), spec.donate, sh)

    def _guard(self):
        """Steady-state transfer guard (opt-in, ``transfer_guard=`` or
        REPRO_SERVING_TRANSFER_GUARD=1): the decode/spec dispatch path runs
        under jax.transfer_guard("disallow"), so any IMPLICIT device<->host
        transfer — a stray numpy input, a silent sync — raises instead of
        silently serializing the pipeline.  The engine's own sanctioned
        movements (cached host-input rebuilds, block-table mirror uploads)
        are explicit jax.device_put calls, and the per-step token readback
        is an explicit jax.device_get outside the guarded region."""
        if self.transfer_guard:
            return jax.transfer_guard("disallow")
        return contextlib.nullcontext()

    # --------------------------------------------------------------- API

    @property
    def queue(self):
        """Admission-queue view (the scheduler): truthy while requests
        wait, ``len()`` for the count — the pre-scheduler deque surface."""
        return self.sched

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               temperature: float = 0.0,
               eos_id: Optional[int] = None,
               latency_class: Optional[str] = None,
               deadline_s: Optional[float] = None) -> int:
        """Queue one request; returns its uid.  ``latency_class`` names a
        configured SchedulerConfig.priority_class (None = the lowest).
        ``deadline_s`` is a relative admission deadline: a request still
        QUEUED when it expires is shed (finish_reason='deadline') instead
        of admitted — activated rows always run to completion."""
        if self._closed:
            raise RuntimeError("submit() on a closed engine")
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if max_new_tokens <= 0:
            raise ValueError(
                f"max_new_tokens must be positive, got {max_new_tokens}"
            )
        if len(prompt) > self.max_len - 1:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds max_len-1={self.max_len - 1}"
            )
        if self.paged:
            # A request whose worst case exceeds one DP shard's sub-pool
            # (== the total pool when unsharded) could never finish: under
            # worst-case admission it would stall the queue head forever,
            # and under on-demand growth it could preempt every other row
            # and STILL run the shard dry mid-decode — fail fast at submit
            # under both policies (this check is also what guarantees a
            # preempted request can always be resumed: its grown prefix
            # stays within one shard's capacity).
            need = min(self.max_len, len(prompt) + max_new_tokens)
            n_blocks = self.kv.blocks_for(need)
            if n_blocks > self.kv.blocks_per_shard:
                raise ValueError(
                    f"request needs {n_blocks} blocks worst-case "
                    f"(prompt {len(prompt)} + max_new {max_new_tokens}) but "
                    f"a pool shard only has {self.kv.blocks_per_shard} "
                    f"(num_blocks={self.kv.num_blocks} over "
                    f"{self.kv.dp_shards} DP shard(s))"
                )
        req = Request(next(self._uid), prompt, max_new_tokens, temperature,
                      eos_id if eos_id is not None else self.eos_id,
                      latency_class=latency_class,
                      class_idx=self.sched.class_index(latency_class))
        if deadline_s is not None:
            if deadline_s <= 0:
                raise ValueError(
                    f"deadline_s must be positive, got {deadline_s}")
            req.deadline = time.monotonic() + deadline_s
            self._has_deadlines = True
        if self.obs.enabled:
            req.t_submit = time.perf_counter()
            self.obs.on_submit(req.uid, len(prompt), max_new_tokens)
        self.sched.submit(req)
        return req.uid

    def _request_keys(self, uids, draft: bool = False) -> np.ndarray:
        """(N, 2) uint32 per-request PRNG key data — fold_in(seed, uid),
        one vmapped dispatch per admission group (keys depend only on the
        engine/draft seed and the uid, never on scheduling)."""
        base = self._draft_base_key if draft else self._base_key
        return np.asarray(jax.vmap(
            lambda u: jax.random.key_data(jax.random.fold_in(base, u))
        )(jnp.asarray(uids, jnp.uint32)))

    def run(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        """Drive until queue + prefills + slots drain.  uid -> generated.

        Admission runs only when it could actually progress (see
        ``_admission_could_progress``) — the host checks are free, and
        calling ``_admit`` while the batch is full or the pool is
        backpressured (the saturated regimes) would drain the step
        pipeline every iteration and forfeit exactly the overlap it
        exists for; a slot/block freed by an in-flight step surfaces when
        step() consumes it, one iteration later."""
        finished: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            if self._parked:
                self._unpark()
            if self._draining:
                self._shed_shutdown()
            if self._has_deadlines and self.sched:
                self._shed_expired()
            for req in self._pop_finished():  # shed/cancelled surface here
                finished[req.uid] = req.generated
            if self._admission_could_progress():
                for req in self._admit():
                    finished[req.uid] = req.generated
            if not (self.active & ~self._stalled).any():
                # The host may only THINK rows are done pending in-flight
                # transfers: flush the ring, then re-check.  Draining may
                # also free blocks a stalled row was waiting on — retry
                # growth before concluding anything about liveness.
                for req in self.drain():
                    finished[req.uid] = req.generated
                if self.paged and self._stalled.any():
                    self._ensure_coverage()
                if not (self.active & ~self._stalled).any():
                    if not self.active.any():
                        if not self.sched and not self._prefilling:
                            if not self._parked:
                                break
                            # Only backoff-parked retries remain and the
                            # device is empty: fast-forward the dispatch
                            # counter to the earliest ready step instead
                            # of spinning empty iterations (the loop-top
                            # _unpark requeues them next pass).
                            self._step_idx = max(
                                self._step_idx,
                                min(s for s, _ in self._parked))
                            continue
                        continue
                    if self._prefilling or self._admission_could_progress():
                        continue  # prefill/admission can still free or fill
                    raise RuntimeError(
                        "KV pool deadlock: every live row is stalled on an "
                        "exhausted block pool with preemption disabled and "
                        "nothing left to drain — enable preemption "
                        "(SchedulerConfig.preempt) or use admission="
                        "'worst_case'"
                    )
            for req in self.step():
                finished[req.uid] = req.generated
        return finished

    # ------------------------------------------------------------- admission

    def _admit(self) -> List[Request]:
        """Admit queued requests (returns any that finish at admission,
        plus any finished by the pipeline drain admission requires).

        Drain discipline: admission reads the host's free-slot / block
        views and scatters fresh per-slot state, so every in-flight step
        must be consumed first — the ring is empty while the prefill roots
        run, and no in-flight entry ever straddles a slot's change of
        occupant."""
        self._drain_ring()
        finished = self._pop_finished()
        finished.extend(
            self._admit_paged() if self.paged else self._admit_dense()
        )
        return finished

    def _obs_finish(self, req: Request) -> None:
        """Report one finished request (TTFT/TPOT from its timestamps)."""
        n = len(req.generated)
        ttft = req.t_first - req.t_submit if req.t_submit else 0.0
        tpot = ((req.t_last - req.t_first) / (n - 1)
                if n > 1 and req.t_last > req.t_first else 0.0)
        self.obs.on_finish(req.uid, n, ttft, tpot)

    def _finish_or_activate(self, req: Request, slot: int, tok: int,
                            finished: List[Request]) -> None:
        """Shared post-prefill bookkeeping for a request's first token."""
        req.slot = slot
        req.generated.append(tok)
        if self.obs.enabled:
            req.t_first = req.t_last = time.perf_counter()
            self.obs.on_first_token(req.uid, slot,
                                    req.t_first - req.t_submit
                                    if req.t_submit else 0.0)
        self.temps[slot] = req.temperature
        self._eos[slot] = -1 if req.eos_id is None else req.eos_id
        self._len_host[slot] = len(req.prompt)
        self._dev_len[slot] = len(req.prompt)
        self._stalled[slot] = False
        self._host_dirty = True
        if self.spec is not None:
            self._k_row[slot] = self.spec.k  # fresh speculation window
        if (req.done or self._len_host[slot] >= self.max_len - 1
                or tok == self._eos[slot]):
            finished.append(req)
            self._mark_finished(req)
            self._retire_slot(slot)
            if self.obs.enabled:
                self._obs_finish(req)
        else:
            self.slots[slot] = req
            self.active[slot] = True

    # ---- paged: reserve blocks, stream prompts chunkwise

    def _free_slots(self, busy=frozenset()) -> List[int]:
        """Free slots in the order they freed (see ``_freed_at``)."""
        return sorted(
            (i for i in range(self.max_batch)
             if not self.active[i] and i not in busy),
            key=lambda i: self._freed_at[i],
        )

    def _retire_slot(self, slot: int) -> None:
        """Shared retirement bookkeeping for EVERY finish path (admission
        finishes and both commit paths): release the slot, invalidate the
        cached host inputs, stamp the freed-order clock, free KV blocks."""
        req = self.slots[slot]
        if req is not None:
            # A retired uid must be able to re-arm the preempt_ready
            # signal if it is ever re-blocked (and the set must not grow
            # unboundedly over a long-running engine).
            self._obs_blocked.discard(req.uid)
        self.slots[slot] = None
        self.active[slot] = False
        self._stalled[slot] = False
        self._dev_len[slot] = 0
        self._host_dirty = True
        self._freed_at[slot] = next(self._free_clock)
        if self.paged:
            self.kv.free(slot)  # blocks reusable immediately
        if self.spec is not None:
            self.draft.free(slot)

    def _admission_could_progress(self) -> bool:
        """Cheap host-side check gating _admit() calls from run(): a
        prefill is mid-flight, or the scheduler head could plausibly land
        in a free slot (paged: and its admission blocks — prompt-only
        under on-demand, worst case under worst_case — fit today's free
        blocks, target AND draft pools), or an SLA preemption could make
        the room — otherwise calling _admit would drain the step pipeline
        every iteration just to back off again.  A blocked round ages the
        waiting class-heads (starvation-free admission)."""
        if self._prefilling:
            return True
        head = self.sched.head()
        if head is None:
            return False
        blocked = bool(self.active.all())
        if not blocked and self.paged:
            n_blocks = self.kv.blocks_for(
                self.sched.admit_tokens(head, self.max_len))
            blocked = self.kv.alloc.free_blocks() < n_blocks
            if not blocked and self.spec is not None:
                blocked = self.draft.kv.alloc.free_blocks() < n_blocks
        if not blocked:
            return True
        if (self.paged and self.sched.preempt
                and self._outranked_victims(head)):
            return True  # SLA preemption will make room in _admit
        self.sched.note_blocked()
        return False

    def _outranked_victims(self, head: Request):
        """(slot, blocks, class_idx) of live rows the head's latency class
        STRICTLY outranks — the only rows SLA admission may evict (equal
        class blocks on backpressure, never thrash)."""
        return [(s, len(self.kv.alloc.owned_by(s)), r.class_idx)
                for s, r in enumerate(self.slots)
                if r is not None and r.class_idx > head.class_idx]

    def _admit_paged(self) -> List[Request]:
        finished: List[Request] = []
        busy = {t.slot for t in self._prefilling}
        while True:
            req = self.sched.head()
            if req is None:
                break
            if self._take_fault("alloc_fail") is not None:
                # Injected allocator failure: admission backs off this
                # round exactly like a dry pool and retries next round —
                # never the idle-shard RuntimeError a real undersized
                # pool raises.
                break
            need = self.sched.admit_tokens(req, self.max_len)
            free = [s for s in self._free_slots(busy)]
            if not free:
                # Batch full: a strictly-outranked live row may be evicted
                # for the head (the ring is drained — _admit's contract).
                victim = (self.sched.pick_victim(self._outranked_victims(req))
                          if self.sched.preempt else None)
                if victim is None:
                    break
                self._preempt(victim, "priority")
                continue
            # Placement: the scheduler orders candidate slots by their DP
            # shard's headroom (emptiest sub-pool first; freed-order within
            # a shard, which IS the old handout when unsharded).  Block
            # reservations are per shard (slot s -> shard s*dp/max_batch),
            # so the head tries every free slot — different slots may land
            # on shards with different headroom.
            slot = None
            for cand in self.sched.slot_order(free, self.kv, self._freed_at):
                if not self.kv.reserve(cand, need):
                    if self.kv.alloc.in_use(self.kv.slot_shard(cand)) == 0:
                        raise RuntimeError(
                            f"request {req.uid} needs "
                            f"{self.kv.blocks_for(need)} blocks but an idle "
                            f"pool shard only has "
                            f"{self.kv.blocks_per_shard}"
                        )
                    continue
                if (self.spec is not None
                        and not self.draft.reserve(cand, need)):
                    # Draft pool is reserved in lockstep with the target's:
                    # on failure roll the target reservation back and try
                    # the next shard (or wait).
                    self.kv.free(cand)
                    continue
                slot = cand
                break
            if slot is None:
                # Every shard exhausted.  SLA preemption first (strictly
                # lower-priority victims only), then FIFO backpressure:
                # flag the live row holding the most blocks as
                # preempt-ready ONCE per blocked request — the victim the
                # pool-dry preemption path actually picks.
                if self.sched.preempt:
                    victim = self.sched.pick_victim(
                        self._outranked_victims(req))
                    if victim is not None:
                        self._preempt(victim, "priority")
                        continue
                if self.obs.enabled and req.uid not in self._obs_blocked:
                    self._obs_blocked.add(req.uid)
                    owners = {t.slot: t.req for t in self._prefilling}
                    owners.update({s: r for s, r in enumerate(self.slots)
                                   if r is not None})
                    cand = max(owners,
                               key=lambda s: len(self.kv.alloc.owned_by(s)),
                               default=None)
                    if cand is not None:
                        self.obs.on_preempt_ready(owners[cand].uid, cand)
                break
            self.sched.pop_head()
            self._obs_blocked.discard(req.uid)
            busy.add(slot)
            if self.obs.enabled:
                self.obs.on_admit(req.uid, slot,
                                  time.perf_counter() - req.t_submit)
            if req.swap is not None:
                self._resume_swap(req, slot)
            else:
                if req.preemptions:
                    self.sched_events["resumes"] += 1
                    if self.obs.enabled:
                        self.obs.on_resume(req.uid, slot, "reprefill")
                self._prefilling.append(_PrefillTask(req, slot))
        if self._prefilling:
            finished.extend(self._prefill_tick())
        return finished

    def _prefill_tick(self) -> List[Request]:
        """Advance every in-flight prefill by ONE chunk (single jit call).
        run() interleaves these ticks with decode steps, so long prompts
        stream in without stalling live rows."""
        c = self.prefill_chunk
        r_rows = self.max_batch
        tasks = self._prefilling[:r_rows]
        tokens = np.zeros((r_rows, c), np.int32)
        starts = np.zeros((r_rows,), np.int32)
        nvalid = np.ones((r_rows,), np.int32)
        fslots = np.full((r_rows,), self.max_batch, np.int32)  # pad = dropped
        budgets = np.zeros((r_rows,), np.int32)
        rkeys = np.zeros((r_rows, 2), np.uint32)
        d_keys = (np.zeros((r_rows, 2), np.uint32)
                  if self.spec is not None else None)
        temps = np.zeros((r_rows,), np.float32)
        bt_rows = np.full((r_rows, self.kv.max_blocks_per_row), -1, np.int32)
        d_bt = (np.full((r_rows, self.kv.max_blocks_per_row), -1, np.int32)
                if self.spec is not None else None)
        fin: List[tuple] = []
        for r, task in enumerate(tasks):
            p = task.req.prompt
            n = min(len(p) - task.pos, c)
            if self.obs.enabled and task.pos == 0:
                self.obs.on_first_chunk(task.req.uid, task.slot)
            tokens[r, :n] = p[task.pos: task.pos + n]
            starts[r] = task.pos
            nvalid[r] = n
            temps[r] = task.req.temperature
            bt_rows[r] = self.kv.table_np[task.slot]
            if d_bt is not None:
                d_bt[r] = self.draft.kv.table_np[task.slot]
            task.pos += n
            if task.pos >= len(p):
                fslots[r] = task.slot
                # Budget after the first sampled token: fresh requests have
                # generated == []; a reprefill-resumed request's prompt
                # already contains its generated tokens, so its budget is
                # what remains AFTER re-sampling the next one.
                budgets[r] = max(0, task.req.max_new_tokens
                                 - len(task.req.generated) - 1)
                fin.append((r, task))
        if fin:
            # Per-request sampling chains for the finishing rows (one
            # batched fold_in dispatch; see Request/_request_keys).
            uids = [t.req.uid for _, t in fin]
            fr = [r for r, _ in fin]
            rkeys[fr] = self._request_keys(uids)
            if d_keys is not None:
                d_keys[fr] = self._request_keys(uids, draft=True)
        tok_dev, starts_dev = jnp.asarray(tokens), jnp.asarray(starts)
        fslots_dev = jnp.asarray(fslots)
        (first, self.kv.pools, self.cache_len, self.last_token,
         self.budget_dev, self.key_data, self._active_dev) = self._chunk_step(
            self.params, self.kv.pools, jnp.asarray(bt_rows),
            tok_dev, starts_dev, jnp.asarray(nvalid),
            fslots_dev, jnp.asarray(budgets), jnp.asarray(rkeys),
            self.cache_len, self.last_token, self.budget_dev, self.key_data,
            jnp.asarray(temps), self._active_dev,
        )
        if self.spec is not None:
            # Stream the same chunk into the draft pools (its own block
            # tables; lengths/last tokens are shared with the target) and
            # reset finishing rows' draft keys to their requests' chains.
            self.draft.pools, self.draft.key_data = self._draft_prefill(
                self.draft.params, self.draft.pools, jnp.asarray(d_bt),
                tok_dev, starts_dev, fslots_dev, self.draft.key_data,
                jnp.asarray(d_keys),
            )
        finished: List[Request] = []
        if fin:
            toks = np.asarray(jax.device_get(first))
            done_tasks = {id(t) for _, t in fin}
            for r, task in fin:
                self._finish_or_activate(task.req, task.slot, int(toks[r]),
                                         finished)
            self._prefilling = [t for t in self._prefilling
                                if id(t) not in done_tasks]
        return finished

    # ---- on-demand growth + preemption (serving/scheduler decisions)

    def _ensure_coverage(self) -> None:
        """Grow every live row's block reservation to cover its next
        dispatch (one token, or the k+1 speculative chunk) — the
        allocate-on-demand half of the scheduler contract.  Growth is
        alloc-only (it appends table entries; the dirty table mirror
        re-uploads at the next dispatch), so it is safe with steps in
        flight.  A row whose shard is dry either stalls (preemption off:
        frozen on device until blocks free) or triggers victim preemption
        (ring drained first — PR 5 drain discipline)."""
        if not self.paged or not self.sched.on_demand:
            return
        look = (self.spec.k + 1) if self.spec is not None else 1
        bs = self.kv.block_size
        for slot in np.flatnonzero(self.active).tolist():
            if not self.active[slot]:
                continue  # retired/preempted by an earlier row's growth
            target = min(int(self._dev_len[slot]) + look, self.max_len)
            covered = len(self.kv.alloc.owned_by(slot)) * bs
            if target <= covered:
                ok = True
            else:
                # A grow is due: opportunistically take one block of
                # slack so the table (re-uploaded whenever it dirties)
                # dirties half as often — but only when the slack fits
                # without stalling or evicting anyone; under pressure
                # fall back to the exact target.
                slacked = min(target + bs, self.max_len)
                ok = slacked > target and self._extend_both(slot, slacked)
                if not ok:
                    ok = self._grow_row(slot, target)
            if not self.active[slot]:
                continue  # the row itself was evicted to make room
            if ok:
                if self._stalled[slot]:
                    self._stalled[slot] = False
                    self._host_dirty = True
            elif not self._stalled[slot]:
                self._stalled[slot] = True
                self._host_dirty = True
                self.sched_events["stalls"] += 1

    def _grow_row(self, slot: int, target: int) -> bool:
        """True once slot's reservation covers ``target`` tokens (or the
        slot is gone).  On shard exhaustion with preemption enabled:
        drain the ring (pending finishes may free blocks), then evict
        most-blocks victims until the growth fits — the growing row is
        itself a candidate, so progress never deadlocks (submit bounds
        every request's worst case to one shard's capacity)."""
        if self._extend_both(slot, target):
            return True
        if not self.sched.preempt:
            return False
        self._drain_ring()
        while self.slots[slot] is not None:
            if self._extend_both(slot, target):
                return True
            victim = self.sched.pick_victim(self._victim_candidates())
            if victim is None:
                return False
            self._preempt(victim, "pool_dry")
        return True  # the drain retired the row; nothing left to cover

    def _extend_both(self, slot: int, target: int) -> bool:
        """Extend target (and draft, in lockstep) coverage; False when
        either pool's shard is dry.  A target-side extension that the
        draft cannot match is kept — harmless over-reservation the retire
        path frees — and retried whole next call."""
        if self._take_fault("alloc_fail") is not None:
            return False  # injected growth failure: caller stalls/evicts
        added = self.kv.extend(slot, target)
        if added is None:
            return False
        d_added = 0
        if self.spec is not None:
            d_added = self.draft.kv.extend(slot, target)
            if d_added is None:
                return False
        grown = added + d_added
        if grown:
            self.sched_events["grown_blocks"] += grown
            if self.obs.enabled:
                req = self.slots[slot]
                self.obs.on_grow(req.uid if req is not None else -1, slot,
                                 grown, self.kv.alloc.in_use())
        return True

    def _victim_candidates(self):
        """(slot, blocks, class_idx) for every live row (any class)."""
        return [(s, len(self.kv.alloc.owned_by(s)), r.class_idx)
                for s, r in enumerate(self.slots) if r is not None]

    def _preempt(self, slot: int, reason: str) -> None:
        """Evict a live row (callers hold the ring drained): swap its KV
        prefix to host (resume='swap') or fold its generated tokens into
        the prompt (resume='reprefill'), release every block through the
        rollback API, and requeue it at the FRONT of its latency class.
        The freed slot and blocks are immediately reusable."""
        req = self.slots[slot]
        n_ctx = int(self._len_host[slot])
        blocks = len(self.kv.alloc.owned_by(slot))
        if self.obs.enabled:
            # The preempt_ready flag and the actual eviction name the same
            # victim — the observability contract ROADMAP item 1 promised.
            self.obs.on_preempt_ready(req.uid, slot)
        swap_bytes = 0
        if self.sched.resume_mode == "swap":
            req.swap = self._swap_out(slot, n_ctx)
            swap_bytes = req.swap.nbytes
        else:
            # Re-prefill resume: the committed prefix (prompt + generated)
            # becomes the prompt.  Greedy streams are unchanged — the
            # re-prefill reproduces the evicted cache exactly and samples
            # the same next token; temperature streams restart their key
            # chain (use resume='swap' to preserve them).
            fold = req.generated[req.prompt_absorbed:]
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(fold, np.int32)])
            req.prompt_absorbed = len(req.generated)
        self.kv.rollback(slot, 0)
        if self.spec is not None:
            self.draft.rollback(slot, 0)
        self.slots[slot] = None
        self.active[slot] = False
        self._stalled[slot] = False
        self._dev_len[slot] = 0
        self._len_host[slot] = 0
        self._host_dirty = True
        self._freed_at[slot] = next(self._free_clock)
        req.slot = None
        req.preemptions += 1
        self.sched.requeue(req)
        self.sched_events["preemptions"] += 1
        self.sched_events["swap_bytes"] += swap_bytes
        if self.obs.enabled:
            self.obs.on_preempt(req.uid, slot, reason, blocks, swap_bytes)

    def _swap_out(self, slot: int, n_ctx: int) -> _SwapPayload:
        """Copy the blocks covering slot's committed context to host (one
        gather per pool leaf + the row's sampling key).  Preemption is off
        the steady-state path, so this D2H is sanctioned — the one-D2H
        step contract is about the decode hot loop.  The payload carries
        a CRC32 so _resume_swap can detect host-side corruption and fall
        back to reprefill instead of scattering garbage KV."""
        n_blocks = self.kv.blocks_for(max(1, n_ctx))
        ids = jnp.asarray(self.kv.alloc.owned_by(slot)[:n_blocks], jnp.int32)
        data = jax.tree.map(
            lambda leaf, ax: np.asarray(
                jax.device_get(jnp.take(leaf, ids, axis=ax))),
            self.kv.pools, self.kv.block_axes)
        key_row = np.asarray(jax.device_get(self.key_data[slot]))
        checksum = _swap_checksum(data)
        req = self.slots[slot]
        if self._take_fault("swap_corrupt",
                            uid=req.uid if req is not None else None):
            # Flip one byte of the first leaf (a private copy —
            # device_get may return read-only views) AFTER checksumming,
            # so the mismatch surfaces at resume time.
            leaves = list(jax.tree.leaves(data))
            bad = np.array(leaves[0], copy=True)
            bad.view(np.uint8).reshape(-1)[0] ^= 0xFF
            leaves[0] = bad
            data = jax.tree.unflatten(jax.tree.structure(data), leaves)
        return _SwapPayload(n_ctx=n_ctx, n_blocks=n_blocks, blocks=data,
                            key_row=key_row, checksum=checksum)

    def _resume_swap(self, req: Request, slot: int) -> None:
        """Re-admit a swap-preempted request by scattering its saved block
        rows into the fresh reservation and restoring the row's device
        state — no recompute, and the PRNG chain continues exactly where
        eviction stopped (temperature streams match an un-preempted run).
        Caller has already reserved admission blocks on ``slot``."""
        pay = req.swap
        if (pay.checksum is not None
                and _swap_checksum(pay.blocks) != pay.checksum):
            # Corrupted swap payload — never scatter it.  Fall back to a
            # reprefill resume over the committed prefix (exact for
            # greedy; temperature restarts the key chain, the documented
            # resume='reprefill' caveat).  The reserved blocks cover the
            # prefix — prompt + generated == n_ctx — so prefill starts
            # immediately on this slot.
            req.swap = None
            fold = req.generated[req.prompt_absorbed:]
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(fold, np.int32)])
            req.prompt_absorbed = len(req.generated)
            self.fault_events["swap_fallbacks"] += 1
            self.sched_events["resumes"] += 1
            if self.obs.enabled:
                self.obs.on_resume(req.uid, slot, "reprefill")
            self._prefilling.append(_PrefillTask(req, slot))
            return
        ids = jnp.asarray(self.kv.alloc.owned_by(slot)[:pay.n_blocks],
                          jnp.int32)
        self.kv.pools = jax.tree.map(
            lambda leaf, ax, host: leaf.at[
                (slice(None),) * ax + (ids,)].set(jnp.asarray(host)),
            self.kv.pools, self.kv.block_axes, pay.blocks)
        g = len(req.generated)
        self.cache_len = self.cache_len.at[slot].set(pay.n_ctx)
        self.last_token = self.last_token.at[slot].set(
            int(req.generated[-1]))
        self.budget_dev = self.budget_dev.at[slot].set(
            req.max_new_tokens - g)
        self.key_data = self.key_data.at[slot].set(jnp.asarray(pay.key_row))
        self._active_dev = self._active_dev.at[slot].set(True)
        if self._sh is not None:
            # Eager scatters can drop the roots' expected placements:
            # repin so donated buffers keep aliasing in place.
            if self.kv.shardings is not None:
                self.kv.pools = jax.device_put(self.kv.pools,
                                               self.kv.shardings)
            row, mat = self._sh.row, self._sh.mat
            self.cache_len = jax.device_put(self.cache_len, row)
            self.last_token = jax.device_put(self.last_token, row)
            self.budget_dev = jax.device_put(self.budget_dev, row)
            self.key_data = jax.device_put(self.key_data, mat)
            self._active_dev = jax.device_put(self._active_dev, row)
        self.slots[slot] = req
        self.active[slot] = True
        self._stalled[slot] = False
        req.slot = slot
        self.temps[slot] = req.temperature
        self._eos[slot] = -1 if req.eos_id is None else req.eos_id
        self._len_host[slot] = pay.n_ctx
        self._dev_len[slot] = pay.n_ctx
        self._host_dirty = True
        req.swap = None
        self.sched_events["resumes"] += 1
        if self.obs.enabled:
            self.obs.on_resume(req.uid, slot, "swap")

    # ---- dense: bucketed batched prefill-admission (PR 1 path)

    @staticmethod
    def _make_buckets(bucket_min: int, max_len: int) -> List[int]:
        buckets = []
        b = bucket_min
        while b < max_len:
            buckets.append(b)
            b *= 2
        buckets.append(max_len)
        return buckets

    def _bucket(self, plen: int) -> int:
        for b in self._buckets:
            if plen <= b:
                return b
        return self.max_len

    def _take_group(self, max_r: int) -> List[Request]:
        """Pop up to max_r queued requests sharing the scheduler head's
        prompt-length bucket (FIFO within the bucket and class)."""
        if not self.sched:
            return []
        if not self._bucketed:
            # Recurrent state: exact-length prefill, one request at a time.
            return [self.sched.pop_head()]
        return self.sched.take_bucket(
            max_r, lambda req: self._bucket(len(req.prompt)))

    def _admit_dense(self) -> List[Request]:
        finished: List[Request] = []
        while self.sched:
            free = self._free_slots()
            if not free:
                break
            group = self._take_group(len(free))
            if not group:
                break
            if self._bucketed:
                plen_pad = self._bucket(max(len(r.prompt) for r in group))
                rows = self.max_batch  # fixed shape: compiles per bucket only
            else:
                plen_pad = len(group[0].prompt)
                rows = 1
            tokens = np.zeros((rows, plen_pad), np.int32)
            plens = np.ones((rows,), np.int32)
            slots = np.full((rows,), self.max_batch, np.int32)  # pad = dropped
            budgets = np.zeros((rows,), np.int32)
            rkeys = np.zeros((rows, 2), np.uint32)
            d_keys = (np.zeros((rows, 2), np.uint32)
                      if self.spec is not None else None)
            temps = np.zeros((rows,), np.float32)
            for r, req in enumerate(group):
                tokens[r, : len(req.prompt)] = req.prompt
                plens[r] = len(req.prompt)
                slots[r] = free[r]
                budgets[r] = max(0, req.max_new_tokens - 1)
                temps[r] = req.temperature
                if self.obs.enabled:
                    self.obs.on_admit(req.uid, free[r],
                                      time.perf_counter() - req.t_submit)
            uids = [req.uid for req in group]
            rkeys[: len(group)] = self._request_keys(uids)
            if d_keys is not None:
                d_keys[: len(group)] = self._request_keys(uids, draft=True)
            slots_dev = jnp.asarray(slots)
            (first, self.cache, self.cache_len, self.last_token,
             self.budget_dev, self.key_data, self._active_dev) = self._prefill(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(plens), slots_dev,
                jnp.asarray(budgets), jnp.asarray(rkeys), self.cache_len,
                self.last_token, self.budget_dev, self.key_data,
                jnp.asarray(temps), self._active_dev,
            )
            if self.spec is not None:
                self.draft.cache, self.draft.key_data = self._draft_prefill(
                    self.draft.params, self.draft.cache,
                    jnp.asarray(tokens), slots_dev, self.draft.key_data,
                    jnp.asarray(d_keys),
                )
            toks = np.asarray(jax.device_get(first))
            for r, req in enumerate(group):
                self._finish_or_activate(req, free[r], int(toks[r]), finished)
        return finished

    # --------------------------------------------------------------- decode

    def step(self) -> List[Request]:
        """One pipelined decode step; returns requests finished.

        Dispatches the next decode (or draft+verify) root immediately, then
        consumes the OLDEST in-flight step's token transfer only once the
        ring holds ``pipeline_depth`` entries — so with depth D the device
        runs up to D steps ahead of the host's emission/free bookkeeping.
        Depth 1 reproduces the unpipelined dispatch->sync sequence exactly.
        At most one D2H transfer is consumed per call."""
        if self._draft_dead and self._step_idx >= self._draft_off_until:
            # A killed draft path re-enables after its cool-down; stale
            # draft-cache entries only lower acceptance (verify stays an
            # exact argmax-prefix check), never correctness.  Drain the
            # ring first: plain-decode entries alias last_token, which
            # the verify root DONATES — switching with them in flight
            # would delete an unconsumed token future.
            self._drain_ring()
            self._draft_dead = False
            self.fault_events["draft_reenables"] += 1
            if self.obs.enabled:
                self.obs.on_degraded("draft", False)
        use_spec = self.spec is not None and not self._draft_dead
        if use_spec and self.spec.dynamic_k and self._ring:
            # Per-row window feedback: step N+1's k_row depends on step N's
            # acceptance, so dynamic-k speculation runs the ring at depth 1.
            self._drain_ring()
        if self.paged and self.sched.on_demand:
            # Grow every live row's reservation to cover this dispatch
            # (alloc-only bookkeeping — safe with steps in flight).
            self._ensure_coverage()
        if use_spec:
            self._dispatch_spec()
        else:
            self._dispatch_decode()
        self._step_idx += 1
        if len(self._ring) >= self.pipeline_depth:
            self._consume_one()
        return self._pop_finished()

    def drain(self) -> List[Request]:
        """Consume every in-flight step (one D2H each, oldest first) and
        return all newly finished requests.  The engine calls this before
        any host bookkeeping that must see a synced view — admission,
        defrag, dynamic-k — and callers may use it to flush the tail."""
        self._drain_ring()
        return self._pop_finished()

    def _drain_ring(self) -> None:
        if self.obs.enabled and self._ring:
            self.obs.on_drain(len(self._ring))
        while self._ring:
            self._consume_one()

    def _pop_finished(self) -> List[Request]:
        out, self._pending_finished = self._pending_finished, []
        return out

    # ------------------------------------------------- fault tolerance

    def _take_fault(self, kind: str, uid: Optional[int] = None):
        """Claim a due injected fault of ``kind`` (None without a plan).

        Fires the telemetry fault event for kinds whose injection IS the
        observable fault; poison_logits instead reports at host-side
        detection (see _quarantine), where the fault actually surfaces."""
        if self._faults is None:
            return None
        sp = self._faults.take(kind, self._step_idx, uid=uid)
        if (sp is not None and self.obs.enabled
                and kind != "poison_logits"):
            self.obs.on_fault(kind, -1 if uid is None else uid,
                              self._step_idx)
        return sp

    def _poison_args(self):
        """Trailing poison input for the chaos-variant sampling roots.

        () when the engine was built without poison specs (the roots then
        take no poison argument).  Otherwise the cached device-zero row
        vector — or a freshly-uploaded vector with NaN at each targeted
        live slot when a poison spec fires this dispatch.  Zeros are an
        EXACT identity on the logits (x + 0.0), so healthy rows and
        non-firing steps stay bit-identical to a fault-free engine."""
        if not self._chaos:
            return ()
        vec = None
        mask = self.active & ~self._stalled
        for slot in np.flatnonzero(mask).tolist():
            req = self.slots[slot]
            if req is None:
                continue
            if self._take_fault("poison_logits", uid=req.uid) is None:
                continue
            if vec is None:
                vec = np.zeros((self.max_batch,), np.float32)
            vec[slot] = np.nan
        row = self._sh.row if self._sh is not None else None
        if vec is not None:
            return (jax.device_put(vec, row),)
        if self._poison_zero is None:
            self._poison_zero = jax.device_put(
                np.zeros((self.max_batch,), np.float32), row)
        return (self._poison_zero,)

    def _mark_finished(self, req: Request, reason: str = "stop") -> None:
        """Stamp the terminal reason (first writer wins) and record the
        request — every exit path funnels through here, so finish_reason
        accounting can never miss one."""
        if req.finish_reason is None:
            req.finish_reason = reason
        self.finished_requests[req.uid] = req

    def _abort(self, req: Request, reason: str) -> None:
        """Terminate a request outside the commit paths (shed / cancel /
        shutdown) and surface it via the pending-finished list the next
        public step()/run() iteration returns."""
        self._mark_finished(req, reason)
        self._pending_finished.append(req)
        if self.obs.enabled:
            self._obs_finish(req)

    def _quarantine(self, slot: int, req: Request,
                    finished: List[Request]) -> None:
        """A poisoned row surfaced in the packed D2H word (POISON_TOKEN,
        or spec n_commit == -1): free the slot immediately — healthy rows
        never stall behind it — then either park the request for a
        backoff'd reprefill retry or finish it with
        ``finish_reason='error'``."""
        if self.obs.enabled:
            self.obs.on_fault("poison_logits", req.uid, self._step_idx)
        action, backoff = self._handler.disposition(req)
        self._retire_slot(slot)
        req.slot = None
        if action == "retry":
            # Reprefill-retry from the committed context (the _preempt
            # reprefill arm): generated tokens fold into the prompt, the
            # request parks until its backoff elapses, then requeues at
            # the front of its class.  The poison token was never
            # appended, so the retried context is clean.
            fold = req.generated[req.prompt_absorbed:]
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(fold, np.int32)])
            req.prompt_absorbed = len(req.generated)
            self._parked.append((self._step_idx + backoff, req))
            self.fault_events["retried"] += 1
            if self.obs.enabled:
                self.obs.on_retry(req.uid, req.retries, backoff)
        else:
            self.fault_events["quarantined"] += 1
            self._mark_finished(req, "error")
            finished.append(req)
            if self.obs.enabled:
                self._obs_finish(req)

    def _degrade_draft(self) -> None:
        """Draft dispatch failed: run plain decode until the cool-down
        elapses (step() re-enables), flagging the degraded component."""
        self._draft_dead = True
        self._draft_off_until = (
            self._step_idx + self._fault_policy.draft_cooldown_steps)
        self.fault_events["draft_kills"] += 1
        if self.obs.enabled:
            self.obs.on_degraded("draft", True)

    def _unpark(self) -> None:
        """Requeue parked poison-retries whose backoff has elapsed (they
        re-enter at the FRONT of their class, like preemption resumes)."""
        due = [(s, r) for s, r in self._parked if s <= self._step_idx]
        if not due:
            return
        self._parked = [(s, r) for s, r in self._parked
                        if s > self._step_idx]
        for _, req in due:
            self.sched.requeue(req)

    def _shed_expired(self) -> None:
        """Admission-side deadline shedding: drop queued requests whose
        deadline passed before they reached a slot (activated rows run to
        completion — a mid-flight abort would waste the work done)."""
        now = time.monotonic()
        expired = [r for r in self.sched.queued()
                   if r.deadline is not None and r.deadline <= now]
        for req in expired:
            self.sched.remove(req.uid)
            self.fault_events["shed"] += 1
            self._abort(req, "deadline")
            if self.obs.enabled:
                self.obs.on_shed(req.uid, "deadline")

    def _shed_shutdown(self) -> None:
        """Drop every queued + parked request as ``shutdown`` (the drain
        discipline: live rows keep decoding to completion)."""
        for req in list(self.sched.queued()):
            self.sched.remove(req.uid)
            self.fault_events["shed"] += 1
            self._abort(req, "shutdown")
            if self.obs.enabled:
                self.obs.on_shed(req.uid, "shutdown")
        for _, req in self._parked:
            self.fault_events["shed"] += 1
            self._abort(req, "shutdown")
            if self.obs.enabled:
                self.obs.on_shed(req.uid, "shutdown")
        self._parked = []

    def cancel(self, uid: int) -> bool:
        """Cancel a request anywhere in its pre-finish lifecycle.

        Queued and backoff-parked requests are dropped outright; a
        mid-prefill request frees its reservation; a LIVE row drains the
        step ring first (in-flight steps may still write its blocks —
        the same interleave invariant admission holds) and is cancelled
        only if it did not finish during the drain.  Returns True iff
        the request was found and ended with finish_reason='cancelled'."""
        req = self.sched.remove(uid)
        if req is not None:
            self._finish_cancel(req)
            return True
        for i, (_, parked) in enumerate(self._parked):
            if parked.uid == uid:
                del self._parked[i]
                self._finish_cancel(parked)
                return True
        for task in self._prefilling:
            if task.req.uid == uid:
                self._prefilling.remove(task)
                if self.paged:
                    self.kv.free(task.slot)
                    if self.spec is not None:
                        self.draft.free(task.slot)
                    self._freed_at[task.slot] = next(self._free_clock)
                self._finish_cancel(task.req)
                return True
        for slot, live in enumerate(self.slots):
            if live is not None and live.uid == uid:
                self._drain_ring()
                if self.slots[slot] is not live:
                    return False  # finished while the ring drained
                self._retire_slot(slot)
                self._finish_cancel(live)
                return True
        return False

    def _finish_cancel(self, req: Request) -> None:
        self.fault_events["cancelled"] += 1
        self._abort(req, "cancelled")
        if self.obs.enabled:
            self.obs.on_shed(req.uid, "cancelled")

    def request_drain(self) -> None:
        """Signal graceful shutdown (serve.py's SIGTERM handler): run()
        stops admitting and sheds queued/parked work as 'shutdown';
        live rows decode to completion."""
        self._draining = True

    def close(self) -> None:
        """Shut the engine down: drain the ring, then finish EVERYTHING
        still inside (queued, parked, prefilling, live) with
        ``finish_reason='shutdown'``.  Idempotent; subsequent submits
        raise.  Requests that finished normally during the final drain
        keep their 'stop' reason."""
        if self._closed:
            return
        self._draining = True
        self._drain_ring()
        self._shed_shutdown()
        for task in list(self._prefilling):
            if self.paged:
                self.kv.free(task.slot)
                if self.spec is not None:
                    self.draft.free(task.slot)
            self.fault_events["shed"] += 1
            self._abort(task.req, "shutdown")
            if self.obs.enabled:
                self.obs.on_shed(task.req.uid, "shutdown")
        self._prefilling = []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            self._retire_slot(slot)
            self.fault_events["shed"] += 1
            self._abort(req, "shutdown")
            if self.obs.enabled:
                self.obs.on_shed(req.uid, "shutdown")
        self._closed = True

    def fault_stats(self) -> Dict[str, object]:
        """Fault accounting: every injected fault (by kind, from the
        plan's fired log) plus the engine's degradation counters — the
        block the BENCH stamps and the chaos tests reconcile."""
        injected = self._faults.counts() if self._faults is not None else {}
        out: Dict[str, object] = {
            "injected": injected,
            "injected_total": int(sum(injected.values())),
            "parked": len(self._parked),
            "degraded": self.degraded_components(),
        }
        out.update(self.fault_events)
        return out

    def degraded_components(self) -> Dict[str, object]:
        """Currently-degraded components, empty when fully healthy (the
        /healthz provider: non-empty answers 503)."""
        out: Dict[str, object] = {}
        if self.spec is not None and self._draft_dead:
            out["draft"] = {"off_until_step": self._draft_off_until}
        stalled = np.flatnonzero(self._stalled).tolist()
        if stalled:
            out["stalled_slots"] = [int(s) for s in stalled]
        if self._draining:
            out["draining"] = True
        return out

    def engine_snapshot(self) -> Dict[str, object]:
        """JSON-serializable engine state for ServingFault post-mortems
        (and the chaos CLI's fault report)."""
        return {
            "step": self._step_idx,
            "ring_depth": len(self._ring),
            "pipeline_depth": self.pipeline_depth,
            "slots": [
                None if r is None else {
                    "uid": r.uid,
                    "generated": len(r.generated),
                    "len": int(self._len_host[s]),
                    "stalled": bool(self._stalled[s]),
                }
                for s, r in enumerate(self.slots)],
            "queued": len(self.sched),
            "parked": len(self._parked),
            "prefilling": len(self._prefilling),
            "pool_free_blocks": (self.kv.alloc.free_blocks()
                                 if self.paged else None),
            "degraded": self.degraded_components(),
            "faults": self.fault_stats(),
        }

    def _host_inputs(self):
        """Device-resident (host_keep, temps, eos[, k_row]) for dispatch,
        rebuilt only when admission/finish bookkeeping dirtied them."""
        if self._host_dirty:
            # Explicit device_put (guard-sanctioned; sharded when meshed).
            # Stalled rows are live but must not advance: host_keep drops
            # them, so the device freezes their entire per-slot state (the
            # same mechanism that freezes finished rows) until growth
            # succeeds and un-stalls them.
            row = self._sh.row if self._sh is not None else None
            keep = self.active & ~self._stalled
            self._keep_dev = jax.device_put(keep, row)
            self._temps_dev = jax.device_put(self.temps, row)
            self._eos_dev = jax.device_put(self._eos, row)
            if self.spec is not None:
                self._k_row_dev = jax.device_put(self._k_row, row)
            if self.paged:
                # Dispatch-order permutation (longest rows first per DP
                # shard).  Any fixed permutation is token-stream neutral —
                # the root un-permutes its logits — so reusing it between
                # dirty events is correct even as lengths advance.
                order = self.sched.row_order(self._dev_len, keep,
                                             self.max_batch, self.dp_shards)
                if order is None:
                    order = np.arange(self.max_batch, dtype=np.int32)
                self._order_dev = jax.device_put(order, row)
            self._host_dirty = False
        return self._keep_dev, self._temps_dev, self._eos_dev

    def _dispatch_decode(self) -> None:
        """Launch one decode root and ring its token future (no sync)."""
        t0 = time.perf_counter()
        mask = self.active & ~self._stalled
        with self._guard(), self.obs.span("serving.dispatch.decode"):
            host_keep, temps, eos = self._host_inputs()
            if self.paged:
                (sampled, self.kv.pools, self.cache_len, self.budget_dev,
                 self.key_data, self._active_dev) = self._decode(
                    self.params, self.kv.pools, self.kv.table_device(),
                    self.last_token, self.cache_len, self.budget_dev,
                    self.key_data, self._active_dev, host_keep, temps, eos,
                    self._order_dev, *self._poison_args(),
                )
            else:
                (sampled, self.cache, self.cache_len, self.budget_dev,
                 self.key_data, self._active_dev) = self._decode(
                    self.params, self.cache, self.last_token, self.cache_len,
                    self.budget_dev, self.key_data, self._active_dev,
                    host_keep, temps, eos, *self._poison_args(),
                )
        self.last_token = sampled
        if self.paged:
            self._dev_len += mask  # each dispatched row writes one entry
        self._note_occupancy(mask)
        self._ring.append(_InFlight(sampled, mask,
                                    time.perf_counter() - t0))
        if self.obs.enabled:
            self._obs_dispatch("decode", mask)

    def _dispatch_spec(self) -> None:
        """Launch one speculative step (fused draft-K root + chunk-verify
        root) and ring its packed committed-token future (no sync)."""
        t0 = time.perf_counter()
        mask = self.active & ~self._stalled
        with self._guard():
            host_keep, temps, eos = self._host_inputs()
            k_row = self._k_row_dev

            with self.obs.span("serving.dispatch.spec_draft"):
                # An injected kill fires BEFORE the root call, so no draft
                # buffer has been donated — engine state is untouched.  A
                # real draft-root error propagates like any other.
                killed = self._take_fault("draft_kill") is not None
                if not killed:
                    (proposals, q_probs, self.draft.pools,
                     self.draft.key_data) = self._spec_draft(
                        self.draft.params, self.draft.pools,
                        self.draft.table_device(),
                        self.last_token, self.cache_len, self.draft.key_data,
                        self._active_dev, host_keep, temps,
                    )
            if killed:
                # Degrade to plain decode (greedy streams are token-
                # identical — verify was always an exact argmax prefix
                # check) and re-enable after the cool-down.
                self._degrade_draft()
                self._dispatch_decode()
                return
            target_cache = self.kv.pools if self.paged else self.cache
            bt = self.kv.table_device() if self.paged else None
            with self.obs.span("serving.dispatch.spec_verify"):
                (pack, target_cache, self.cache_len, self.last_token,
                 self.budget_dev, self.key_data,
                 self._active_dev) = self._spec_verify(
                    self.params, target_cache, bt, self.last_token, proposals,
                    q_probs, self.cache_len, self.budget_dev, self.key_data,
                    self._active_dev, host_keep, temps, eos, k_row,
                    *self._poison_args(),
                )
        if self.paged:
            self.kv.pools = target_cache
        else:
            self.cache = target_cache
        if self.paged:
            # Conservative device-length advance: verify may write the full
            # k+1 proposal entries before rolling back to the accepted
            # prefix; _commit_spec reconciles once acceptance is known.
            self._dev_len += (self.spec.k + 1) * mask
        self._note_occupancy(mask)
        self._ring.append(_InFlight(pack, mask, time.perf_counter() - t0,
                                    spec=True, k_row=self._k_row.copy()))
        if self.obs.enabled:
            self._obs_dispatch("spec", mask)

    def _note_occupancy(self, mask: np.ndarray) -> None:
        """Accumulate per-dispatch occupancy: live committed tokens over
        reserved pool tokens (the on-demand payoff metric — worst-case
        admission reserves far more than it has committed) and live rows
        per step (mean batch occupancy).  Host ints only."""
        self._occ_rows_sum += int(mask.sum())
        self._occ_rows_steps += 1
        if not self.paged:
            return
        reserved = self.kv.alloc.in_use() * self.kv.block_size
        if reserved > 0:
            live = int(self._len_host[mask].sum())
            self._occ_live_frac_sum += live / reserved
            self._occ_samples += 1

    def _obs_dispatch(self, kind: str, mask: np.ndarray) -> None:
        """Step-dispatch telemetry: ring depth, live rows, per-shard pool
        occupancy — all host ints the engine already tracks."""
        pool = peaks = None
        live_tok = reserved_tok = None
        if self.paged:
            alloc = self.kv.alloc
            pool = [alloc.in_use(s) for s in range(alloc.num_shards)]
            peaks = self.kv.blocks_per_shard
            reserved_tok = alloc.in_use() * self.kv.block_size
            live_tok = int(self._len_host[mask].sum())
        self.obs.on_step_dispatch(kind, len(self._ring), int(mask.sum()),
                                  self._ring[-1].dispatch_s, pool, peaks,
                                  live_tok, reserved_tok)

    def _consume_one(self) -> None:
        """Sync the oldest in-flight step's tokens (the ONE D2H this step
        ever costs) and run its emission/finish/free bookkeeping, appending
        newly finished requests to the pending list."""
        entry = self._ring.popleft()
        sp = self._take_fault("straggler")
        if sp is not None:
            time.sleep(sp.delay_s)  # simulated hung transfer
        t0 = time.perf_counter()
        with self.obs.span("serving.ring_sync"):
            toks = np.asarray(jax.device_get(entry.tokens))
        t_sync = time.perf_counter() - t0
        if sp is not None:
            t_sync += sp.delay_s  # the sleep IS the stall being modeled
        self.decode_transfers += 1
        dur = entry.dispatch_s + t_sync
        if self._watchdog is not None:
            verdict = self._watchdog.observe(dur)
            if verdict != "ok":
                self.fault_events["straggler_slow"] += 1
                if verdict == "trip":
                    self.fault_events["straggler_trips"] += 1
                if self.obs.enabled:
                    self.obs.on_straggler(verdict, dur)
        timeout = self._fault_policy.step_timeout_s
        if timeout is not None and dur > timeout:
            raise ServingFault(
                f"engine step exceeded hard timeout: {dur:.3f}s > "
                f"{timeout}s (dispatch {entry.dispatch_s:.3f}s + sync "
                f"{t_sync:.3f}s)", kind="step_timeout",
                step=self._step_idx, snapshot=self.engine_snapshot())
        if entry.spec:
            finished = self._commit_spec(entry, toks)
        else:
            finished = self._commit_decode(entry, toks)
        self._pending_finished.extend(finished)
        t_host = time.perf_counter() - t0 - t_sync
        self.step_device_wait_s.append(t_sync)
        self.step_host_s.append(t_host)
        self.step_times.append(entry.dispatch_s + t_sync + t_host)
        if self.obs.enabled:
            self.obs.on_step_consume("spec" if entry.spec else "decode",
                                     t_sync, t_host)

    def _commit_decode(self, entry: _InFlight,
                       toks: np.ndarray) -> List[Request]:
        # A slot live in entry.mask whose request has since been retired
        # (it finished in an OLDER ring entry) carries a garbage token the
        # device either masked or wrote into the slot's still-reserved
        # space: skip it.  FIFO consumption guarantees the converse — a
        # row still live here was device-active at this entry's dispatch.
        live = np.fromiter((r is not None for r in self.slots), bool,
                           self.max_batch)
        adv = entry.mask & live
        self._len_host += adv
        finished: List[Request] = []
        now = time.perf_counter() if self.obs.enabled else 0.0
        for slot, req in enumerate(self.slots):
            if req is None or not adv[slot]:
                continue
            tok = int(toks[slot])
            if tok == POISON_TOKEN:
                # Device-side finite check tripped (NaN/Inf logits): the
                # packed D2H word carries the verdict, so detection costs
                # no extra transfer.  The sentinel is never emitted.
                self._quarantine(slot, req, finished)
                continue
            req.generated.append(tok)
            if self.obs.enabled:
                req.t_last = now
                self.obs.on_commit(req.uid, slot, 1)
            if (req.done or self._len_host[slot] >= self.max_len - 1
                    or tok == self._eos[slot]):
                finished.append(req)
                self._mark_finished(req)
                self._retire_slot(slot)
                if self.obs.enabled:
                    self._obs_finish(req)
        return finished

    def _commit_spec(self, entry: _InFlight,
                     toks: np.ndarray) -> List[Request]:
        k = self.spec.k
        toks_mat = toks[:, : k + 1]
        n_commit, m_acc = toks[:, k + 1], toks[:, k + 2]
        finished: List[Request] = []
        now = time.perf_counter() if self.obs.enabled else 0.0
        for slot, req in enumerate(self.slots):
            if req is None or not entry.mask[slot]:
                continue
            if int(n_commit[slot]) < 0:
                # Verify-side finite check: n_commit == -1 flags NaN/Inf
                # logits for this row (its budget was NOT charged) —
                # quarantine before any speculative accounting.
                self._quarantine(slot, req, finished)
                continue
            m = int(m_acc[slot])
            k_eff = int(entry.k_row[slot])
            req.spec_proposed += k_eff
            req.spec_accepted += m
            self.spec_proposed += k_eff
            self.spec_accepted += m
            self.spec_step_rows += 1
            if self.obs.enabled:
                self.obs.on_spec_row(k_eff, m)
            self._len_host[slot] += m + 1  # entries committed to cache
            if self.paged:
                # Dispatch advanced _dev_len by the conservative k+1;
                # the cache actually kept m+1 — reconcile the difference
                # so coverage targets track the committed length.
                self._dev_len[slot] -= k - m
            if self.spec.dynamic_k:
                if m == k_eff:
                    self._k_row[slot] = min(k, k_eff + 1)
                elif m == 0:
                    self._k_row[slot] = max(1, k_eff - 1)
                self._host_dirty = True
            done = False
            appended = 0
            base_len = self._len_host[slot] - (m + 1)
            for j in range(int(n_commit[slot])):
                tok = int(toks_mat[slot, j])
                req.generated.append(tok)
                self.spec_committed += 1
                appended += 1
                # Sequential-decode finish semantics: cached length after
                # this token is base_len + j + 1.
                if (req.done or base_len + j + 1 >= self.max_len - 1
                        or tok == self._eos[slot]):
                    done = True
                    break
            if self.obs.enabled and appended:
                req.t_last = now
                self.obs.on_commit(req.uid, slot, appended)
            if done:
                finished.append(req)
                self._mark_finished(req)
                self._retire_slot(slot)
                if self.obs.enabled:
                    self._obs_finish(req)
        return finished

    # ------------------------------------------------------------ telemetry

    def stats(self) -> Dict[str, float]:
        """Decode-step timing summary (seconds) + throughput proxy.

        ``device_wait_*`` is the D2H sync stall per consumed step and
        ``host_*`` the emission/free bookkeeping that follows — the two
        halves the pipeline overlaps with the device's next step."""
        if not self.step_times:
            # Fully-keyed zero snapshot: callers (serve.py, benchmarks,
            # dashboards) index timing keys unconditionally — an engine
            # that never stepped must not crash them or emit NaN.
            return {
                "steps": 0,
                "step_mean_s": 0.0, "step_p50_s": 0.0,
                "step_p90_s": 0.0, "step_p99_s": 0.0,
                "device_wait_mean_s": 0.0, "device_wait_p50_s": 0.0,
                "host_mean_s": 0.0, "host_p50_s": 0.0,
                "pipeline_depth": self.pipeline_depth,
                "live_rows": int(self.active.sum()),
            }
        ts = np.asarray(self.step_times)
        dw = np.asarray(self.step_device_wait_s)
        hb = np.asarray(self.step_host_s)
        n_live = max(1, int(self.active.sum()))
        return {
            "steps": len(ts),
            "step_mean_s": float(ts.mean()),
            "step_p50_s": float(np.percentile(ts, 50)),
            "step_p90_s": float(np.percentile(ts, 90)),
            "step_p99_s": float(np.percentile(ts, 99)),
            "device_wait_mean_s": float(dw.mean()),
            "device_wait_p50_s": float(np.percentile(dw, 50)),
            "host_mean_s": float(hb.mean()),
            "host_p50_s": float(np.percentile(hb, 50)),
            "pipeline_depth": self.pipeline_depth,
            "live_rows": n_live,
        }

    def spec_stats(self) -> Dict[str, float]:
        """Speculative-decoding accounting: acceptance rate and committed
        tokens per live row-step (>= 1.0; the speedup proxy)."""
        if self.spec is None:
            return {}
        return {
            "k": self.spec.k,
            "dynamic_k": bool(self.spec.dynamic_k),
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "committed": self.spec_committed,
            "acceptance_rate": self.spec_accepted / max(1, self.spec_proposed),
            "committed_per_row_step":
                self.spec_committed / max(1, self.spec_step_rows),
            "draft_hbm_bytes": self.draft.hbm_bytes(),
        }

    def scheduler_stats(self) -> Dict[str, object]:
        """Scheduling policy + lifecycle accounting: admission policy,
        preempt/resume/grow counters, and the occupancy means the
        overcommit benchmark reports (live committed tokens / reserved
        pool tokens per dispatch; live rows per step)."""
        occ = (self._occ_live_frac_sum / self._occ_samples
               if self._occ_samples else None)
        rows = (self._occ_rows_sum / self._occ_rows_steps
                if self._occ_rows_steps else 0.0)
        return {
            "admission_policy": self.sched.cfg.admission,
            "preempt_enabled": self.sched.preempt,
            "resume_mode": self.sched.resume_mode,
            "priority_classes": list(self.sched.cfg.priority_classes),
            "preempt_count": self.sched_events["preemptions"],
            "swap_bytes": self.sched_events["swap_bytes"],
            "grown_blocks": self.sched_events["grown_blocks"],
            "resumes": self.sched_events["resumes"],
            "stalls": self.sched_events["stalls"],
            "occupancy_live_frac": occ,
            "mean_live_rows": rows,
            "queued": len(self.sched),
        }

    def mesh_shape(self) -> Dict[str, int]:
        """The serving mesh as {dp, tp, devices} ((1, 1, 1) when meshless
        — the layout every sharded stat reduces to on one device)."""
        if self.par is None:
            return {"dp": 1, "tp": 1, "devices": 1}
        m = self.par.mesh
        dp = int(np.prod([m.shape[a] for a in self.par.dp_axes]))
        tp = int(m.shape[self.par.tp_axis]) if self.par.tp_axis else 1
        return {"dp": dp, "tp": tp, "devices": int(m.size)}

    def cache_stats(self) -> Dict[str, float]:
        """Cache memory accounting: HBM bytes (global + per device) +
        live/reserved tokens."""
        live = int((self._len_host * self.active).sum())
        if self.paged:
            s = dict(self.kv.stats(), layout="paged")
        else:
            slab = int(sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.cache)
            ))
            s = {
                "layout": "dense",
                "tokens_capacity": self.max_batch * self.max_len,
                "cache_hbm_bytes": slab,
                "dp_shards": self.dp_shards,
                # The slab shards over its batch dim: each device holds
                # max_batch / dp rows (the whole slab when unsharded).
                "per_device_cache_hbm_bytes": slab // self.dp_shards,
            }
        s["mesh"] = self.mesh_shape()
        s["live_tokens"] = live
        if self.spec is not None:
            s["draft_hbm_bytes"] = self.draft.hbm_bytes()
        return s

    def defrag(self) -> int:
        """Compact live blocks to the lowest pool ids (paged only).
        Returns the number of blocks moved (target + draft pools).

        Drains the step pipeline first: the move map comes from the host
        allocator, which must have consumed every in-flight step's frees
        before permuting the pools (finishes surface from the next public
        step()/_admit()/drain())."""
        if not self.paged:
            return 0
        self._drain_ring()
        moved = len(self.kv.defrag())
        if self.spec is not None:
            moved += len(self.draft.kv.defrag())
        if self.obs.enabled:
            self.obs.on_defrag(moved)
        return moved

    def telemetry_snapshot(self) -> Dict:
        """Full observability snapshot (metrics + trace tail + engine
        stats) — ``{}`` when the engine runs without telemetry."""
        return self.obs.snapshot(self) if self.obs.enabled else {}
