"""Step builders: train_step / prefill_step / decode_step with shardings.

These are the jit roots the launcher, serving engine and dry-run all share.
Every step is a pure function over (params, [opt/cache], batch) pytrees; the
sharding trees returned by ``step_shardings`` plug straight into
``jax.jit(in_shardings=..., out_shardings=...)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.api import Model
from repro.models.losses import chunked_xent_from_hidden, next_token_xent
from repro.obs.profiler import wrap_root
from repro.optim import (
    AdamWConfig,
    AdamWState,
    apply_updates,
    init_state,
    state_pspecs,
)
from repro.optim.grad import roundtrip
from repro.parallel.sharding import Parallelism, param_pspecs
from repro.runtime.fault import GuardConfig, guarded_update


@dataclasses.dataclass(frozen=True)
class StepConfig:
    aux_weight: float = 0.01  # MoE load-balance loss weight
    chunked_loss: int = 0  # >0: seq-chunked xent (memory optimization)
    grad_compress: bool = False  # int8+error-feedback DP gradients
    guard: Optional[GuardConfig] = GuardConfig()


# ------------------------------------------------------------------- train

def make_train_step(
    model: Model, opt_cfg: AdamWConfig, step_cfg: StepConfig = StepConfig()
) -> Callable:
    cfg = model.cfg

    def loss_fn(params, batch):
        kwargs = {}
        if cfg.is_encdec:
            kwargs["frames"] = batch["frames"]
        elif "patches" in batch:
            kwargs["patches"] = batch["patches"]
        if step_cfg.chunked_loss and not cfg.is_encdec:
            hidden, _, aux = model.apply(
                params, batch["tokens"], mode="train", output="hidden", **kwargs
            )
            unemb = params.get("unembed", params["embed"])
            loss = chunked_xent_from_hidden(
                hidden, unemb, batch["tokens"], chunk=step_cfg.chunked_loss,
                mask=batch.get("loss_mask"),
            )
        else:
            logits, _, aux = model.apply(
                params, batch["tokens"], mode="train", **kwargs
            )
            loss = next_token_xent(logits, batch["tokens"], batch.get("loss_mask"))
        return loss + step_cfg.aux_weight * aux, (loss, aux)

    def train_step(params, opt_state, batch, grad_error=None):
        (total, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        new_error = grad_error
        if step_cfg.grad_compress:
            grads, new_error = roundtrip(grads, grad_error)
        new_params, new_opt, metrics = apply_updates(params, grads, opt_state, opt_cfg)
        metrics = dict(metrics, loss=loss, aux=aux)
        if step_cfg.guard is not None:
            (new_params, new_opt), bad = guarded_update(
                loss, metrics["grad_norm"], (new_params, new_opt),
                (params, opt_state), step_cfg.guard,
            )
            metrics["bad_step"] = bad
        if step_cfg.grad_compress:
            return new_params, new_opt, metrics, new_error
        return new_params, new_opt, metrics

    return train_step


# ------------------------------------------------------------------- serve

def make_prefill_step(model: Model, max_len: int) -> Callable:
    cfg = model.cfg

    def prefill_step(params, batch):
        b = batch["tokens"].shape[0]
        cache = model.init_cache(b, max_len)
        kwargs = {}
        if cfg.is_encdec:
            kwargs["frames"] = batch["frames"]
        elif "patches" in batch:
            kwargs["patches"] = batch["patches"]
        logits, cache, _ = model.apply(
            params, batch["tokens"], mode="prefill", cache=cache, **kwargs
        )
        return logits[:, -1:], cache

    return prefill_step


def make_decode_step(model: Model) -> Callable:
    def decode_step(params, cache, batch):
        logits, cache, _ = model.apply(
            params,
            batch["tokens"],
            mode="decode",
            cache=cache,
            cache_len=batch["cache_len"],
        )
        return logits, cache

    return decode_step


# ------------------------------------------------------- serving jit roots
#
# The serving engine keeps ALL per-slot state (cache/pools, lengths, last
# tokens, active flags, PRNG keys) on device; these step builders are its
# only jit roots.  PRNG keys travel as raw (B, 2) uint32 key data so they
# scatter/gather with plain .at indexing.
#
# Every step donates its cache/state buffers (the *_DONATE argnum tuples
# below plug into jax.jit(donate_argnums=...)): XLA aliases each donated
# input to the same-shaped output, so the multi-MB cache is updated in place
# instead of being copied every step.  Engine rule: host-originated arrays
# (active mirror, temps, eos ids, admission token batches) are rebuilt per
# call and never donated; device state is always reassigned from the step's
# outputs, never reused.
#
# ALL finish detection happens ON DEVICE: the decode steps compare the
# sampled token against each row's eos id, decrement the row's remaining
# token ``budget`` (set at admission to max_new_tokens - 1) and check the
# max_len bound, clearing the row's active flag in the same fused call — so
# a finished row stops sampling/writing on the very next step with no host
# round-trip, WHATEVER its finish reason.  The host learns about finishes
# for free from the token vector it already transfers, and composes its own
# (possibly stale) view through the ``host_keep`` mask input.
#
# Device-authoritative exits are what make the engine's depth-K step
# pipeline sound: step N+1 can be dispatched before step N's tokens reach
# the host because a row that finishes at step N is masked by the DEVICE
# from N+1 on — the chained device state (cache, cache_len, budget, keys,
# active) is bit-identical whether the host consumed step N's transfer
# before or after dispatching N+1.  ``host_keep`` is then a pure safety
# net (it can only re-mask rows the device already masked, or rows whose
# slot the host has since retired — whose writes are garbage by contract).

def sample_tokens(key_data: jax.Array, logits: jax.Array, temps: jax.Array):
    """Vectorized per-row sampling: greedy where temps <= 0, categorical at
    logits/temp otherwise, each row drawing from its own PRNG key.

    key_data: (B, 2) uint32, logits: (B, V), temps: (B,) float32.
    Returns (new_key_data (B, 2), tokens (B,) int32).
    """

    def one(kd, lg, t):
        new_key, sub = jax.random.split(jax.random.wrap_key_data(kd))
        greedy = jnp.argmax(lg, -1).astype(jnp.int32)
        drawn = jax.random.categorical(sub, lg / jnp.maximum(t, 1e-6))
        tok = jnp.where(t > 0.0, drawn.astype(jnp.int32), greedy)
        return jax.random.key_data(new_key), tok

    return jax.vmap(one)(key_data, logits, temps)


def set_cache_rows(cache, rows, slots: jax.Array):
    """Write R per-row cache slices into batch rows ``slots``, one scatter
    per leaf.  Out-of-range slot indices are dropped (mode="drop"), which
    admission uses to pad request groups to a fixed batch shape without
    clobbering live rows."""

    def walk(c, r, name=""):
        if isinstance(c, dict):
            return {k: walk(c[k], r[k], k) for k in c}
        ax = c.ndim - _CACHE_LEAF_RULES[name][0]
        idx = (slice(None),) * ax + (slots,)
        return c.at[idx].set(r.astype(c.dtype), mode="drop")

    return walk(cache, rows)


# Device-side poison sentinel in the packed D2H token word.  Sampled
# vocab ids are >= 0 and the disabled-eos sentinel is -1, so -2 is free:
# a row whose logits go non-finite reports POISON_TOKEN instead of a
# token and clears its own active flag, and the host quarantines it off
# the transfer it already performs — no extra D2H word, no host check
# on the healthy path.
POISON_TOKEN = -2


def _sample_advance_exit(logits, last_token, cache_len, budget, key_data,
                         active, host_keep, temps, eos, max_len):
    """Shared decode-step tail: batched sampling, inactive-row masking,
    per-row length advance, and the device-side finish update (EOS sample,
    exhausted token budget, or the max_len-1 cache bound — every reason a
    host would retire the row).  Both decode builders (dense slab and
    paged) MUST share this so their sampling/exit semantics cannot
    diverge."""
    act = jnp.logical_and(active, host_keep)
    # Always-on finite check: a poisoned row (NaN/Inf logits — numerical
    # cliff or injected fault) folds POISON_TOKEN into the existing D2H
    # word and retires itself on device.  Healthy rows are untouched:
    # the wheres below select their exact sampled values bit-for-bit.
    bad = jnp.logical_and(
        act, jnp.logical_not(jnp.isfinite(logits[:, 0]).all(axis=-1)))
    new_kd, sampled = sample_tokens(key_data, logits[:, 0], temps)
    # Inactive rows FREEZE all their per-slot state — token, length,
    # budget, and PRNG key alike.  The key freeze is what makes extra
    # pipeline dispatches true no-ops: a retired slot's key chain must not
    # depend on how many garbage steps ran before the host caught up, or
    # the slot's next occupant would sample a different stream per depth.
    sampled = jnp.where(act, sampled, last_token)
    sampled = jnp.where(bad, POISON_TOKEN, sampled)
    key_data = jnp.where(act[:, None], new_kd, key_data)
    adv = act.astype(jnp.int32)
    cache_len = cache_len + adv
    budget = budget - adv
    alive = jnp.logical_and(budget > 0, cache_len < max_len - 1)
    # The active flag FREEZES too for host-masked rows (retired rows are
    # already device-dead, so freezing matches the old always-clear there):
    # a live row the scheduler temporarily withholds — stalled on block
    # growth — must still be device-active when dispatches resume, not
    # permanently retired by the masked no-op steps in between.
    new_active = jnp.logical_and(jnp.logical_and(act, sampled != eos), alive)
    new_active = jnp.logical_and(new_active, jnp.logical_not(bad))
    active = jnp.where(host_keep, new_active, active)
    return sampled, cache_len, budget, key_data, active


# donate: cache, cache_len, budget, key_data, active.  last_token is NOT
# donated: the sampled vector a step emits IS the next step's last_token,
# and the pipeline ring holds it for a still-pending D2H — donating it to
# step N+1 would delete step N's in-flight transfer.  At (B,) int32 the
# un-aliased copy is noise next to the cache.
DECODE_DONATE = (1, 3, 4, 5, 6)


def make_decode_sample_step(model: Model, max_len: int) -> Callable:
    """Fused decode + batched sampling + device-side finish exits: one
    jitted call per engine step and zero host round-trips.  Inactive rows
    keep their last_token and cache_len (their sampled garbage is masked
    out on device).  ``eos`` is a per-row token id (-1 disables); a row
    that samples its eos id, spends its last budgeted token, or hits the
    max_len-1 cache bound drops out of ``active`` in the same call.

    The chaos-variant root (RootContext.chaos) appends a trailing (B,)
    float32 ``poison`` input added to the logits: a zero vector is an
    exact identity (x + 0.0 bit-preserves finite floats), so streams are
    token-identical until the fault harness swaps in a NaN row."""

    def decode_sample_step(params, cache, last_token, cache_len, budget,
                           key_data, active, host_keep, temps, eos,
                           poison=None):
        act = jnp.logical_and(active, host_keep)
        logits, cache, _ = model.apply(
            params, last_token[:, None], mode="decode",
            cache=cache, cache_len=cache_len,
        )
        if poison is not None:
            logits = logits + poison[:, None, None]
        sampled, cache_len, budget, key_data, active = _sample_advance_exit(
            logits, last_token, cache_len, budget, key_data, active,
            host_keep, temps, eos, max_len,
        )
        return sampled, cache, cache_len, budget, key_data, active

    return decode_sample_step


# donate: pools, cache_len, budget, key_data, active (last_token stays
# un-donated — the ring may hold it for an in-flight D2H, see DECODE_DONATE)
PAGED_DECODE_DONATE = (1, 4, 5, 6, 7)


def make_paged_decode_step(model: Model, max_len: int) -> Callable:
    """Paged twin of ``decode_sample_step``: the cache is a shared block
    pool addressed through ``block_tables`` (see serving/kvcache).  Rows
    that are not effectively active get their block-table row forced to -1
    so their cache writes DROP — a freed slot's blocks may already belong to
    another request, so masking the write (not just the sampled token) is a
    correctness requirement, not an optimization."""

    def paged_decode_step(params, pools, block_tables, last_token, cache_len,
                          budget, key_data, active, host_keep, temps, eos,
                          row_order, poison=None):
        act = jnp.logical_and(active, host_keep)
        bt_eff = jnp.where(act[:, None], block_tables, -1)
        # Zero dead rows' lengths for the attention call only (real
        # cache_len still advances below): a retired slot keeps its final
        # cache_len until reuse, and the packed kernel's page loop runs to
        # the LONGEST length in each row pack — one stale 16-page row
        # would drag its whole pack through 16 junk-page DMAs per step.
        cl_eff = jnp.where(act, cache_len, 0)
        # Attention runs in scheduler-chosen row order (longest-first per
        # DP shard, dead rows last) so each packed-kernel row pack shares
        # page-loop trip counts.  Per-row math is row-independent, so
        # un-permuting the logits makes the permutation invisible to
        # sampling — and every donated array stays in slot order, keeping
        # the donation aliases intact.
        inv = jnp.argsort(row_order)
        logits_s, pools, _ = model.apply(
            params, jnp.take(last_token, row_order)[:, None], mode="decode",
            cache=pools, cache_len=jnp.take(cl_eff, row_order),
            block_tables=jnp.take(bt_eff, row_order, axis=0),
        )
        logits = jnp.take(logits_s, inv, axis=0)
        if poison is not None:
            logits = logits + poison[:, None, None]
        sampled, cache_len, budget, key_data, active = _sample_advance_exit(
            logits, last_token, cache_len, budget, key_data, active,
            host_keep, temps, eos, max_len,
        )
        return sampled, pools, cache_len, budget, key_data, active

    return paged_decode_step


# donate: pools, cache_len, last_token, budget, key_data, active
PAGED_PREFILL_DONATE = (1, 9, 10, 11, 12, 14)


def make_paged_prefill_chunk_step(model: Model) -> Callable:
    """One chunk of streaming (chunked) prefill into the paged cache, for up
    to R requests at once.  Each row r writes ``tokens[r]`` at logical
    positions ``starts[r]..starts[r]+C-1`` of its block-table row and
    attends causally over its own prefix — so a very long prompt is admitted
    as a sequence of fixed-width chunk calls interleaved with decode steps
    instead of one monolithic prefill that stalls the running batch.

    Only ``nvalid[r]`` leading tokens of a row's chunk are real; garbage
    writes beyond them land at positions that are either masked by causality
    / cache_len or overwritten before ever becoming visible, and writes past
    the row's block reservation drop on the -1 table entries.  ``fslots[r]``
    is the row's engine slot when this chunk FINISHES its prompt (>= nslots
    otherwise): finishing rows commit cache_len/last_token/budget/keys/
    active (``budgets[r]`` is the request's remaining token budget,
    max_new_tokens - 1, feeding the device-side exit; ``row_keys[r]`` the
    REQUEST's own PRNG key, fold_in(engine seed, uid) — per-request chains
    make sampled streams independent of slot assignment and admission
    timing, which the depth-K pipeline shifts) and sample their first
    token from the last real position's logits.
    Any row count R works (rows past the prompts are padding); the engine
    compiles one per rung of ``serving.engine.prefill_tick_rungs``."""

    def paged_prefill_chunk_step(params, pools, bt_rows, tokens, starts,
                                 nvalid, fslots, budgets, row_keys,
                                 cache_len, last_token, budget, key_data,
                                 temps, active):
        logits, pools, _ = model.apply(
            params, tokens, mode="decode",
            cache=pools, cache_len=starts, block_tables=bt_rows,
        )
        last = jnp.take_along_axis(
            logits, jnp.maximum(nvalid - 1, 0)[:, None, None], axis=1
        )
        row_keys, first = sample_tokens(row_keys, last[:, 0], temps)
        cache_len = cache_len.at[fslots].set(starts + nvalid, mode="drop")
        last_token = last_token.at[fslots].set(first, mode="drop")
        budget = budget.at[fslots].set(budgets, mode="drop")
        key_data = key_data.at[fslots].set(row_keys, mode="drop")
        active = active.at[fslots].set(True, mode="drop")
        return first, pools, cache_len, last_token, budget, key_data, active

    return paged_prefill_chunk_step


# donate: cache, cache_len, last_token, budget, key_data, active
PREFILL_ADMIT_DONATE = (1, 7, 8, 9, 10, 12)


def make_prefill_admit_step(model: Model, max_len: int,
                            kv_quant: bool = False) -> Callable:
    """Batched multi-request admission in one jitted call: prefill R
    prompts (right-padded to a shared bucket length P), scatter their fresh
    row caches into the engine cache (replacing any previous occupant's
    rows wholesale), set per-slot lengths / last tokens / budgets / keys,
    and sample every row's first token.

    ``slots`` entries >= max_batch mark padding rows: all their writes drop,
    so admission groups keep a fixed (max_batch, P) shape and the engine
    compiles once per prompt-length bucket, not once per prompt length.
    """

    def prefill_admit_step(params, cache, tokens, plens, slots, budgets,
                           row_keys, cache_len, last_token, budget,
                           key_data, temps, active):
        row_cache = model.init_cache(tokens.shape[0], max_len,
                                     kv_quant=kv_quant)
        logits, row_cache, _ = model.apply(
            params, tokens, mode="prefill", cache=row_cache
        )
        # Last REAL position's logits per row (prompts are right-padded).
        last = jnp.take_along_axis(logits, (plens - 1)[:, None, None], axis=1)
        row_keys, first = sample_tokens(row_keys, last[:, 0], temps)
        cache = set_cache_rows(cache, row_cache, slots)
        cache_len = cache_len.at[slots].set(plens, mode="drop")
        last_token = last_token.at[slots].set(first, mode="drop")
        budget = budget.at[slots].set(budgets, mode="drop")
        key_data = key_data.at[slots].set(row_keys, mode="drop")
        active = active.at[slots].set(True, mode="drop")
        return first, cache, cache_len, last_token, budget, key_data, active

    return prefill_admit_step


# ------------------------------------------------- speculative decoding
#
# Self-speculative roots (serving/spec/): the draft root runs K+1 sequential
# cheap decodes over the DRAFT cache in one jitted call (no per-token host
# round-trips; the proposal matrix and draft probs stay on device and flow
# straight into the verify root), and the verify root feeds the K proposals
# through the same S>1 chunk-decode path chunked prefill uses, then performs
# batched accept/resample (serving/spec/verify.py) and rolls the per-row
# cache lengths to the accepted prefix — the length rollback IS the cache
# rollback: stale entries past cache_len are invisible to attention and get
# overwritten by the next chunk.  Both roots take ``block_tables=None`` for
# the dense-slab layout (the dense decode path accepts S >= 1 chunks).


# donate: pools (draft), key_data (draft)
SPEC_DRAFT_DONATE = (1, 5)


def make_spec_draft_step(model: Model, k: int) -> Callable:
    """Fused draft-K root: K+1 sequential single-token decodes of the DRAFT
    model (feed t0, sample d_1; ... feed d_{K-1}, sample d_K; feed d_K to
    cache it), emitting the (B, K) proposal matrix and the (B, K, V) draft
    probs the verifier's accept/resample needs.  Feeding all K+1 tokens —
    one more than it samples — keeps the draft cache a superset of every
    committable prefix, so draft and target lengths stay equal and no
    catch-up chunk ever exists.  Inactive rows' paged writes drop via the
    -1-forced block table; dense writes land past their own row's frozen
    cache_len, where admission's wholesale row rewrite erases them."""

    def spec_draft_step(params, pools, block_tables, last_token, cache_len,
                        key_data, active, host_keep, temps):
        act = jnp.logical_and(active, host_keep)
        bt_eff = None
        if block_tables is not None:
            bt_eff = jnp.where(act[:, None], block_tables, -1)
        # Dead rows attend at length 0 (see paged_decode_step) and their
        # key chain freezes across the scan (see _sample_advance_exit).
        cl_eff = jnp.where(act, cache_len, 0)
        kd_in = key_data

        def body(carry, i):
            tok, pools, kd = carry
            logits, pools, _ = model.apply(
                params, tok[:, None], mode="decode", cache=pools,
                cache_len=cl_eff + i, block_tables=bt_eff,
            )
            lg = logits[:, 0]
            q = jax.nn.softmax(
                lg.astype(jnp.float32)
                / jnp.maximum(temps, 1e-6)[:, None], axis=-1
            )
            kd, nxt = sample_tokens(kd, lg, temps)
            return (nxt, pools, kd), (nxt, q)

        (_, pools, key_data), (toks, qs) = jax.lax.scan(
            body, (last_token, pools, key_data),
            jnp.arange(k + 1, dtype=jnp.int32),
        )
        key_data = jnp.where(act[:, None], key_data, kd_in)
        proposals = toks[:k].T  # (B, K); the (K+1)-th sample is discarded
        q_probs = jnp.moveaxis(qs[:k], 0, 1)  # (B, K, V)
        return proposals, q_probs, pools, key_data

    return spec_draft_step


# donate: pools (target), last_token, cache_len, budget, key_data, active
SPEC_VERIFY_DONATE = (1, 3, 6, 7, 8, 9)


def make_spec_verify_step(model: Model, k: int, max_len: int) -> Callable:
    """Chunk-verification root: run the target on [t0, d_1..d_K] (one S=K+1
    chunk decode against the cache — the paged S>1 path, or the dense slab's
    chunked twin), accept/resample on device (greedy = exact prefix match;
    temperature = Leviathan accept u < p/q + residual resample, preserving
    the target distribution exactly), advance each row's cache_len by the
    m+1 committed entries [t0, d_1..d_m] — the cache-rollback contract —
    and fuse the device-side finish scan over the committed tokens (EOS,
    exhausted token ``budget``, or the max_len-1 cache bound, mirroring the
    plain decode root so pipelined spec steps stay depth-invariant).

    Returns a single packed int32 matrix for the step's ONE D2H transfer:
    ``[out_tokens (K+1) | n_commit | m]`` per row, where out_tokens is
    [d_1..d_m, t_new, fill], n_commit truncates at the first committed EOS,
    and m is the raw acceptance count for the engine's accounting."""

    from repro.serving.spec.verify import verify_tail

    def spec_verify_step(params, pools, block_tables, last_token, proposals,
                         q_probs, cache_len, budget, key_data, active,
                         host_keep, temps, eos, k_row, poison=None):
        act = jnp.logical_and(active, host_keep)
        bt_eff = None
        if block_tables is not None:
            bt_eff = jnp.where(act[:, None], block_tables, -1)
        chunk = jnp.concatenate([last_token[:, None], proposals], axis=1)
        logits, pools, _ = model.apply(
            params, chunk, mode="decode", cache=pools, cache_len=cache_len,
            block_tables=bt_eff,
        )
        if poison is not None:
            logits = logits + poison[:, None, None]
        # Always-on finite check, the spec twin of _sample_advance_exit's:
        # a poisoned row signals the host through the n_commit word it
        # already packs (-1 is unreachable: healthy n_commit >= 0) and
        # retires itself on device.  Healthy rows' wheres are identities.
        bad = jnp.logical_and(
            act, jnp.logical_not(jnp.isfinite(logits).all(axis=(1, 2))))
        new_kd, m, t_new, out_tokens = verify_tail(
            key_data, logits, q_probs, proposals, temps, k_row
        )
        # Dead rows freeze their keys (see _sample_advance_exit) so extra
        # pipelined dispatches cannot perturb a reused slot's sample chain.
        key_data = jnp.where(act[:, None], new_kd, key_data)
        t_new = jnp.where(act, t_new, last_token)
        n_raw = jnp.where(act, m + 1, 0)
        cache_len = cache_len + n_raw
        idx = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
        committed = idx < n_raw[:, None]
        is_eos = jnp.logical_and(out_tokens == eos[:, None], committed)
        any_eos = is_eos.any(axis=1)
        n_commit = jnp.where(any_eos, jnp.argmax(is_eos, axis=1) + 1, n_raw)
        # The host emits n_commit tokens (minus any it truncates at its own
        # budget/max_len bound — but those bounds clear `active` right here,
        # so the row is device-dead before the next dispatch either way).
        # Poisoned rows commit nothing: their budget freezes and the pack
        # carries the -1 quarantine sentinel instead of a commit count.
        budget = budget - jnp.where(bad, 0, n_commit)
        n_commit = jnp.where(bad, -1, n_commit)
        alive = jnp.logical_and(budget > 0, cache_len < max_len - 1)
        # Freeze (not clear) the active flag for host-masked rows — see
        # _sample_advance_exit: a scheduler-stalled row must stay
        # device-active across the masked steps it sits out.
        new_active = jnp.logical_and(
            jnp.logical_and(act, jnp.logical_not(any_eos)), alive
        )
        new_active = jnp.logical_and(new_active, jnp.logical_not(bad))
        active = jnp.where(host_keep, new_active, active)
        pack = jnp.concatenate(
            [out_tokens.astype(jnp.int32), n_commit[:, None].astype(jnp.int32),
             jnp.where(jnp.logical_and(act, jnp.logical_not(bad)), m, 0,
                       )[:, None].astype(jnp.int32)], axis=1,
        )
        return pack, pools, cache_len, t_new, budget, key_data, active

    return spec_verify_step


# donate: pools/cache (draft), key_data (draft)
PAGED_DRAFT_PREFILL_DONATE = (1, 6)
DENSE_DRAFT_PREFILL_DONATE = (1, 4)


def make_paged_draft_prefill_step(model: Model) -> Callable:
    """Draft twin of the paged prefill chunk root: stream the SAME token
    chunk into the draft pools — no sampling; the only engine-state write
    is resetting finishing rows' draft PRNG keys to the REQUEST's own draft
    chain (fold_in(draft seed, uid) — the scheduling-independence argument
    of the target roots applies to draft proposals too).  Garbage tokens
    past a row's nvalid follow the target root's argument: masked by
    causality/cache_len or overwritten before visible; writes past the
    row's draft reservation drop on -1 table entries."""

    def paged_draft_prefill_step(params, pools, bt_rows, tokens, starts,
                                 fslots, key_data, row_keys):
        _, pools, _ = model.apply(
            params, tokens, mode="decode", cache=pools, cache_len=starts,
            block_tables=bt_rows, output="hidden",
        )
        key_data = key_data.at[fslots].set(row_keys, mode="drop")
        return pools, key_data

    return paged_draft_prefill_step


def make_dense_draft_prefill_step(model: Model, max_len: int,
                                  kv_quant: bool = False) -> Callable:
    """Draft twin of the dense prefill-admit root: prefill the prompt batch
    through the DRAFT params, scatter the fresh rows into the draft slab
    (pad slots >= max_batch drop, exactly like admission), and reset the
    admitted rows' draft PRNG keys to their requests' own chains."""

    def dense_draft_prefill_step(params, cache, tokens, slots, key_data,
                                 row_keys):
        row_cache = model.init_cache(tokens.shape[0], max_len,
                                     kv_quant=kv_quant)
        _, row_cache, _ = model.apply(
            params, tokens, mode="prefill", cache=row_cache, output="hidden"
        )
        key_data = key_data.at[slots].set(row_keys, mode="drop")
        return set_cache_rows(cache, row_cache, slots), key_data

    return dense_draft_prefill_step


# ----------------------------------------------------- serving root registry
#
# Machine-readable registry of every serving jit root: the engine builds its
# jitted steps from these specs (builder + donate_argnums + sharding hook),
# and the static auditor (repro.analysis) enumerates them mechanically —
# lowering each root from abstract inputs and checking the transfer/donation/
# sharding/dtype contracts without running a decode step.  Adding a serving
# root means adding a RootSpec here; the auditor picks it up for free.

@dataclasses.dataclass(frozen=True)
class RootContext:
    """Everything needed to (re)build a serving root's jit callable and its
    abstract input pytrees: the model facade plus the engine geometry knobs.
    ``num_blocks=None`` resolves exactly like PagedKVCache's default
    (serving/kvcache.resolve_num_blocks), so audits trace the same pool the
    engine would allocate."""

    model: Model
    max_batch: int = 8
    max_len: int = 512
    kv_quant: bool = False
    prefill_chunk: int = 64
    block_size: int = 16
    num_blocks: Optional[int] = None
    spec_k: int = 4
    bucket: int = 16          # representative admission prompt bucket
    bucketed: bool = True     # models.api.prefill_pad_safe(model)
    dp_shards: int = 1
    # Chaos-variant roots: the steady sampling roots (decode /
    # paged_decode / spec_verify) take a trailing (B,) float32 poison
    # input added to the logits, so a FaultPlan can NaN one row's step
    # without recompiling.  Off (the default), roots keep their exact
    # pre-chaos signatures — the fault harness costs nothing when absent.
    chaos: bool = False

    @property
    def resolved_num_blocks(self) -> int:
        from repro.serving.kvcache import resolve_num_blocks

        return resolve_num_blocks(self.max_batch, self.max_len,
                                  self.block_size, self.num_blocks,
                                  self.dp_shards)

    @property
    def max_blocks_per_row(self) -> int:
        return -(-self.max_len // self.block_size)

    # Aval pytrees (no allocation): the cache trees every root threads.

    def cache_avals(self):
        return jax.eval_shape(
            lambda: self.model.init_cache(self.max_batch, self.max_len,
                                          kv_quant=self.kv_quant)
        )

    def pool_avals(self):
        return jax.eval_shape(
            lambda: self.model.init_paged_cache(self.resolved_num_blocks,
                                                self.block_size,
                                                kv_quant=self.kv_quant)
        )


@dataclasses.dataclass(frozen=True)
class RootSpec:
    """One serving jit root.

    ``kind`` pins the root's D2H contract class: "steady" roots run in the
    pipelined decode loop and must emit EXACTLY one device->host transfer
    (the ``d2h`` output indices), "admission" roots may sync one first-token
    vector when rows finish their prompt, "draft" roots emit nothing.

    ``build(ctx)`` returns the pure step function; ``abstract_inputs(ctx,
    params)`` its positional-argument aval pytrees (mirroring the engine's
    dispatch call exactly); ``shardings(sh, ctx, draft_params=None)`` the
    (in, out) NamedSharding pair from a ServingShardings bundle.  Spec-root
    arg 0 is the DRAFT params tree (``needs_draft``) — the auditor traces
    those with the target's avals (same architecture, any well-formed params
    pytree lowers identically)."""

    name: str
    layout: str  # "dense" | "paged"
    kind: str    # "steady" | "admission" | "draft"
    donate: Tuple[int, ...]
    d2h: Tuple[int, ...]
    build: Callable[[RootContext], Callable]
    abstract_inputs: Callable[[RootContext, Any], Tuple[Any, ...]]
    shardings: Callable
    needs_draft: bool = False


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


def _row_avals(b: int):
    """(i32, bool, f32, keys) per-slot aval helpers."""
    return (_sds((b,), jnp.int32), _sds((b,), jnp.bool_),
            _sds((b,), jnp.float32), _sds((b, 2), jnp.uint32))


def _chaos_tail(ctx: RootContext):
    """Trailing poison-input aval for chaos-variant sampling roots."""
    if not ctx.chaos:
        return ()
    return (_sds((ctx.max_batch,), jnp.float32),)


def _decode_inputs(ctx: RootContext, params):
    b = ctx.max_batch
    i32, boo, f32, keys = _row_avals(b)
    return (params, ctx.cache_avals(), i32, i32, i32, keys, boo, boo, f32,
            i32) + _chaos_tail(ctx)


def _paged_decode_inputs(ctx: RootContext, params):
    b = ctx.max_batch
    i32, boo, f32, keys = _row_avals(b)
    bt = _sds((b, ctx.max_blocks_per_row), jnp.int32)
    return (params, ctx.pool_avals(), bt, i32, i32, i32, keys, boo, boo,
            f32, i32, i32) + _chaos_tail(ctx)


def _paged_prefill_chunk_inputs(ctx: RootContext, params):
    b = ctx.max_batch
    i32, boo, f32, keys = _row_avals(b)
    bt = _sds((b, ctx.max_blocks_per_row), jnp.int32)
    toks = _sds((b, ctx.prefill_chunk), jnp.int32)
    return (params, ctx.pool_avals(), bt, toks, i32, i32, i32, i32, keys,
            i32, i32, i32, keys, f32, boo)


def _prefill_admit_inputs(ctx: RootContext, params):
    b = ctx.max_batch
    i32, boo, f32, keys = _row_avals(b)
    rows = b if ctx.bucketed else 1
    r_i32, _, r_f32, r_keys = _row_avals(rows)
    toks = _sds((rows, min(ctx.bucket, ctx.max_len)), jnp.int32)
    return (params, ctx.cache_avals(), toks, r_i32, r_i32, r_i32, r_keys,
            i32, i32, i32, keys, r_f32, boo)


def _spec_cache_avals(ctx: RootContext, layout: str):
    if layout == "paged":
        bt = _sds((ctx.max_batch, ctx.max_blocks_per_row), jnp.int32)
        return ctx.pool_avals(), bt
    return ctx.cache_avals(), None


def _spec_draft_inputs(layout):
    def inputs(ctx: RootContext, params):
        b = ctx.max_batch
        i32, boo, f32, keys = _row_avals(b)
        cache, bt = _spec_cache_avals(ctx, layout)
        return (params, cache, bt, i32, i32, keys, boo, boo, f32)

    return inputs


def _spec_verify_inputs(layout):
    def inputs(ctx: RootContext, params):
        b, k = ctx.max_batch, ctx.spec_k
        i32, boo, f32, keys = _row_avals(b)
        cache, bt = _spec_cache_avals(ctx, layout)
        props = _sds((b, k), jnp.int32)
        qs = _sds((b, k, ctx.model.cfg.vocab_size), jnp.float32)
        return (params, cache, bt, i32, props, qs, i32, i32, keys, boo, boo,
                f32, i32, i32) + _chaos_tail(ctx)

    return inputs


def _draft_prefill_paged_inputs(ctx: RootContext, params):
    b = ctx.max_batch
    i32, _, _, keys = _row_avals(b)
    bt = _sds((b, ctx.max_blocks_per_row), jnp.int32)
    toks = _sds((b, ctx.prefill_chunk), jnp.int32)
    return (params, ctx.pool_avals(), bt, toks, i32, i32, keys, keys)


def _draft_prefill_dense_inputs(ctx: RootContext, params):
    b = ctx.max_batch
    _, _, _, keys = _row_avals(b)
    rows = b if ctx.bucketed else 1
    r_i32, _, _, r_keys = _row_avals(rows)
    toks = _sds((rows, min(ctx.bucket, ctx.max_len)), jnp.int32)
    return (params, ctx.cache_avals(), toks, r_i32, keys, r_keys)


def serving_root_registry(layout: str,
                          spec: bool = False) -> Tuple[RootSpec, ...]:
    """Every serving jit root for one cache layout (plus the speculative
    roots when ``spec``) — the engine's and the static auditor's single
    source of truth for builder/donation/sharding/D2H wiring.

    Every build is wrapped in ``repro.obs.profiler.wrap_root``: a
    ``jax.named_scope`` naming the root in profiler timelines / HLO dumps.
    The scope is metadata-only (no ops, no transfers) and UNCONDITIONAL, so
    engine and auditor always trace the same instrumented computation —
    the contract audits run on exactly what serves."""
    if layout not in ("dense", "paged"):
        raise ValueError(f"unknown cache layout {layout!r}")
    paged = layout == "paged"
    roots = []
    if paged:
        roots.append(RootSpec(
            "paged_decode", "paged", "steady",
            PAGED_DECODE_DONATE, (0,),
            lambda ctx: wrap_root(
                make_paged_decode_step(ctx.model, ctx.max_len),
                "paged_decode"),
            _paged_decode_inputs,
            lambda sh, ctx, draft_params=None: sh.paged_decode(
                chaos=ctx.chaos),
        ))
        roots.append(RootSpec(
            "paged_prefill_chunk", "paged", "admission",
            PAGED_PREFILL_DONATE, (0,),
            lambda ctx: wrap_root(
                make_paged_prefill_chunk_step(ctx.model),
                "paged_prefill_chunk"),
            _paged_prefill_chunk_inputs,
            lambda sh, ctx, draft_params=None: sh.paged_prefill_chunk(),
        ))
    else:
        roots.append(RootSpec(
            "decode", "dense", "steady",
            DECODE_DONATE, (0,),
            lambda ctx: wrap_root(
                make_decode_sample_step(ctx.model, ctx.max_len), "decode"),
            _decode_inputs,
            lambda sh, ctx, draft_params=None: sh.decode(chaos=ctx.chaos),
        ))
        roots.append(RootSpec(
            "prefill_admit", "dense", "admission",
            PREFILL_ADMIT_DONATE, (0,),
            lambda ctx: wrap_root(
                make_prefill_admit_step(ctx.model, ctx.max_len,
                                        kv_quant=ctx.kv_quant),
                "prefill_admit"),
            _prefill_admit_inputs,
            lambda sh, ctx, draft_params=None: sh.prefill_admit(
                bucketed=ctx.bucketed),
        ))
    if spec:
        roots.append(RootSpec(
            "spec_draft", layout, "draft",
            SPEC_DRAFT_DONATE, (),
            lambda ctx: wrap_root(
                make_spec_draft_step(ctx.model, ctx.spec_k), "spec_draft"),
            _spec_draft_inputs(layout),
            lambda sh, ctx, draft_params=None: sh.spec_draft(
                draft_params if draft_params is not None else sh.params,
                paged),
            needs_draft=True,
        ))
        roots.append(RootSpec(
            "spec_verify", layout, "steady",
            SPEC_VERIFY_DONATE, (0,),
            lambda ctx: wrap_root(
                make_spec_verify_step(ctx.model, ctx.spec_k, ctx.max_len),
                "spec_verify"),
            _spec_verify_inputs(layout),
            lambda sh, ctx, draft_params=None: sh.spec_verify(
                paged, chaos=ctx.chaos),
        ))
        if paged:
            roots.append(RootSpec(
                "draft_prefill", "paged", "draft",
                PAGED_DRAFT_PREFILL_DONATE, (),
                lambda ctx: wrap_root(
                    make_paged_draft_prefill_step(ctx.model),
                    "draft_prefill"),
                _draft_prefill_paged_inputs,
                lambda sh, ctx, draft_params=None: sh.draft_prefill_paged(
                    draft_params if draft_params is not None else sh.params),
                needs_draft=True,
            ))
        else:
            roots.append(RootSpec(
                "draft_prefill", "dense", "draft",
                DENSE_DRAFT_PREFILL_DONATE, (),
                lambda ctx: wrap_root(
                    make_dense_draft_prefill_step(
                        ctx.model, ctx.max_len, kv_quant=ctx.kv_quant),
                    "draft_prefill"),
                _draft_prefill_dense_inputs,
                lambda sh, ctx, draft_params=None: sh.draft_prefill_dense(
                    draft_params if draft_params is not None else sh.params),
                needs_draft=True,
            ))
    return tuple(roots)


# -------------------------------------------------------------- shardings

# KV caches are SEQUENCE-sharded over the model axis (context parallelism):
# it sidesteps the non-divisible-head-count archs (chatglm kv=2, phi3 h=40,
# whisper h=12) and scales to 512k caches; batch==1 long-context cells fold
# the DP axes into the sequence dim instead.
_CACHE_LEAF_RULES = {
    # leaf name -> (base ndim, (batch_dim, seq_dim, chan_dim))
    "k": (4, 1, None),
    "v": (4, 1, None),
    "c_kv": (3, 1, None),
    "k_rope": (3, 1, None),
    "h": (3, None, 1),
    "conv": (3, None, 2),
    "state": (4, None, 1),
    "shift_t": (2, None, None),
    "shift_c": (2, None, None),
    "k_scale": (3, 1, None),
    "v_scale": (3, 1, None),
}


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def cache_pspecs(cache_shapes, par: Parallelism):
    """PartitionSpec tree for a cache pytree (stack dims -> None prefix)."""
    mesh = par.mesh
    dp = par.dp
    tp = par.tp_axis

    def walk(tree, name=""):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        base_ndim, seq_dim, chan_dim = _CACHE_LEAF_RULES[name]
        pad = len(tree.shape) - base_ndim
        spec = [None] * len(tree.shape)
        shape = tree.shape
        b = shape[pad]
        batch_ok = mesh is None or b % _axis_size(mesh, dp) == 0
        if batch_ok and mesh is not None:
            spec[pad] = dp
        if seq_dim is not None and mesh is not None:
            t = shape[pad + seq_dim]
            if batch_ok:
                if t % _axis_size(mesh, tp) == 0:
                    spec[pad + seq_dim] = tp
            else:
                # batch=1 long-context: fold DP axes into the sequence dim.
                all_axes = tuple(par.dp_axes) + (tp,)
                if t % _axis_size(mesh, all_axes) == 0:
                    spec[pad + seq_dim] = all_axes
                elif t % _axis_size(mesh, tp) == 0:
                    spec[pad + seq_dim] = tp
        if chan_dim is not None and mesh is not None:
            c = shape[pad + chan_dim]
            if c % _axis_size(mesh, tp) == 0:
                spec[pad + chan_dim] = tp
        return P(*spec)

    return walk(cache_shapes)


def batch_pspecs(batch_shapes, par: Parallelism):
    mesh = par.mesh

    def walk(tree):
        if isinstance(tree, dict):
            return {k: walk(v) for k, v in tree.items()}
        ok = mesh is None or tree.shape[0] % _axis_size(mesh, par.dp) == 0
        lead = par.dp if (ok and mesh is not None) else None
        return P(*([lead] + [None] * (len(tree.shape) - 1)))

    return walk(batch_shapes)


def logits_pspec(batch: int, vocab: int, par: Parallelism) -> P:
    mesh = par.mesh
    b_ok = mesh is not None and batch % _axis_size(mesh, par.dp) == 0
    v_ok = mesh is not None and vocab % _axis_size(mesh, par.tp_axis) == 0
    return P(par.dp if b_ok else None, None, par.tp_axis if v_ok else None)


def sanitize_pspecs(pspec_tree, shape_tree, mesh):
    """Drop sharding entries that don't divide the dim (jit boundaries
    require exact divisibility, unlike internal GSPMD constraints)."""

    def fix(spec, leaf):
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        out = []
        for dim, e in zip(leaf.shape, entries):
            if e is not None and dim % _axis_size(mesh, e) != 0:
                e = None
            out.append(e)
        return P(*out)

    return jax.tree.map(
        fix, pspec_tree, shape_tree, is_leaf=lambda x: isinstance(x, P)
    )


def named(tree_pspec, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_pspec,
        is_leaf=lambda x: isinstance(x, P),
    )


# ------------------------------------------------- serving root shardings
#
# ServingShardings pins EXPLICIT in/out NamedShardings for every serving
# jit root over a DP x TP serving mesh (launch/mesh.make_serving_mesh):
#
#   * weights: TP-sharded via the existing param_pspecs (factored NSVD
#     layers all-reduce rank-k partials instead of d_model — the
#     compression shrinks the TP collective),
#   * per-slot state (last_token, cache_len, key_data, active flags) and
#     every host-built (B, ...) input (temps, eos, token chunks, block
#     tables): data-parallel over slots,
#   * the cache: dense slab over its batch dim, paged pools over their
#     block dim (models.api.serving_cache_pspecs), replicated over TP.
#
# Explicitness matters twice: donated buffers alias only when the donated
# input's sharding equals its output's (both pinned here, keeping the
# engine's in-place-cache contract), and unpinned outputs would let GSPMD
# pick a different layout than the next step's input — a silent recompile
# per step.  On a (1, 1) mesh every spec below is a no-op layout, so the
# sharded engine reproduces the single-device path bit-for-bit.

def _dp_entry(par: Parallelism, max_batch: int):
    """Spec entry for slot-indexed dims; None when slots don't divide DP
    (jit boundaries need exact divisibility — the engine then also keeps
    its block pools unsharded so host bookkeeping matches the layout)."""
    n = _axis_size(par.mesh, par.dp)
    return par.dp if max_batch % n == 0 else None


class ServingShardings:
    """NamedSharding bundles for the serving engine's jit roots.

    ``cache`` is the layout-aware cache sharding tree (dense slab or paged
    pools — models.api.serving_cache_pspecs); the draft cache shares it by
    construction (same arch, same pool geometry)."""

    def __init__(self, par: Parallelism, params, cache_shardings,
                 max_batch: int):
        mesh = par.mesh
        dp = _dp_entry(par, max_batch)
        ns = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
        self.par = par
        self.rep = ns()              # scalars / replicated host inputs
        self.row = ns(dp)            # (B,) per-slot state
        self.mat = ns(dp, None)      # (B, X): keys, tables, token chunks
        self.mat3 = ns(dp, None, None)  # (B, K, V) draft probs
        self.params = self.tree(params)
        self.cache = cache_shardings  # NamedSharding tree (layout-aware)

    def tree(self, shapes):
        """Param shardings for a (possibly factored/compressed) params
        pytree: the existing param_pspecs rules, sanitized against the
        actual leaf shapes (jit boundaries need exact divisibility)."""
        specs = sanitize_pspecs(param_pspecs(shapes), shapes, self.par.mesh)
        return named(specs, self.par.mesh)

    # Per-root (in_shardings, out_shardings); argument orders mirror the
    # step builders above.  ``params`` defaults to the target's tree — spec
    # roots pass the draft's (factored leaves shard identically by rule,
    # but shapes differ, so sanitization must see the right tree).

    def decode(self, params=None, chaos: bool = False):
        p = params or self.params
        tail = (self.row,) if chaos else ()
        return ((p, self.cache, self.row, self.row, self.row, self.mat,
                 self.row, self.row, self.row, self.row) + tail,
                (self.row, self.cache, self.row, self.row, self.mat,
                 self.row))

    def paged_decode(self, params=None, chaos: bool = False):
        p = params or self.params
        tail = (self.row,) if chaos else ()
        return ((p, self.cache, self.mat, self.row, self.row, self.row,
                 self.mat, self.row, self.row, self.row, self.row,
                 self.row) + tail,
                (self.row, self.cache, self.row, self.row, self.mat,
                 self.row))

    def paged_prefill_chunk(self):
        return ((self.params, self.cache, self.mat, self.mat, self.row,
                 self.row, self.row, self.row, self.mat, self.row, self.row,
                 self.row, self.mat, self.row, self.row),
                (self.row, self.cache, self.row, self.row, self.row,
                 self.mat, self.row))

    def prefill_admit(self, bucketed: bool = True):
        """``bucketed=False`` (pad-sensitive archs): admission batches are
        exact-length with rows=1, which cannot split over DP — the (R, ...)
        admission inputs and the sampled-token output stay replicated while
        cache/state keep their slot sharding (the scatter crosses shards
        under GSPMD)."""
        r = self.row if bucketed else self.rep
        m = self.mat if bucketed else self.rep
        return ((self.params, self.cache, m, r, r, r, m,
                 self.row, self.row, self.row, self.mat, r, self.row),
                (r, self.cache, self.row, self.row, self.row, self.mat,
                 self.row))

    def spec_draft(self, draft_params, paged: bool):
        bt = self.mat if paged else None
        return ((draft_params, self.cache, bt, self.row, self.row, self.mat,
                 self.row, self.row, self.row),
                (self.mat, self.mat3, self.cache, self.mat))

    def spec_verify(self, paged: bool, chaos: bool = False):
        bt = self.mat if paged else None
        tail = (self.row,) if chaos else ()
        return ((self.params, self.cache, bt, self.row, self.mat, self.mat3,
                 self.row, self.row, self.mat, self.row, self.row, self.row,
                 self.row, self.row) + tail,
                (self.mat, self.cache, self.row, self.row, self.row,
                 self.mat, self.row))

    def draft_prefill_paged(self, draft_params):
        return ((draft_params, self.cache, self.mat, self.mat, self.row,
                 self.row, self.mat, self.mat),
                (self.cache, self.mat))

    def draft_prefill_dense(self, draft_params):
        return ((draft_params, self.cache, self.mat, self.row, self.mat,
                 self.mat),
                (self.cache, self.mat))


def train_shardings(params_shape, par: Parallelism, batch_shapes, fsdp: bool = False):
    """(in_shardings, out_shardings) pspec trees for the train step."""
    p_specs = param_pspecs(params_shape, fsdp_axes=par.dp_axes if fsdp else None)
    opt_specs = state_pspecs(params_shape, p_specs, par.dp_axes)
    b_specs = batch_pspecs(batch_shapes, par)
    metrics = {
        "loss": P(), "aux": P(), "grad_norm": P(), "lr": P(), "bad_step": P()
    }
    return (p_specs, opt_specs, b_specs), (p_specs, opt_specs, metrics)


def eval_shape_opt_state(params_shape) -> AdamWState:
    return jax.eval_shape(init_state, params_shape)
