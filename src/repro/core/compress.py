"""Compression orchestrator: dense param pytree -> factored param pytree.

Runs on host (numpy/float64) per matrix, GPTQ-style.  Handles stacked
(scan-over-layers) kernels by compressing each slice against its per-layer
Gram with a shared rank, producing stacked factors that keep the model
scannable.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, Mapping, MutableMapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .lowrank import factors_to_params
from .nsvd import decomposition_diagnostics, nested_compress
from .plan import CompressionConfig, CompressionPlan, build_plan
from .ratio import rank_for_ratio

logger = logging.getLogger(__name__)

# GramStore on-disk schema.  1 = the original unstamped npz layout; 2 adds
# the "__schema__" stamp so future layout changes can migrate instead of
# silently misreading arrays.  Bump when the array layout changes.
GRAM_STORE_SCHEMA = 2


class GramStore:
    """name -> (gram (n,n) fp64, absmean (n,), token_count).

    Filled by the calibration runner; consumed here.  ``fallback`` lets
    per-expert grams defer to the shared layer gram when an expert saw too
    few tokens for a well-conditioned Gram (DESIGN.md §7).
    """

    def __init__(self):
        self._grams: Dict[str, np.ndarray] = {}
        self._absmean: Dict[str, np.ndarray] = {}
        self._counts: Dict[str, float] = {}

    def update(self, key: str, gram: np.ndarray, absmean: np.ndarray, count: float):
        if key in self._grams:
            # In place, upcasting each element: the same float64 sums as
            # ``acc + float64(gram)`` without two Gram-sized temporaries.
            np.add(self._grams[key], gram, out=self._grams[key])
            np.add(self._absmean[key], absmean, out=self._absmean[key])
            self._counts[key] += count
        else:
            self._grams[key] = np.asarray(gram, np.float64).copy()
            self._absmean[key] = np.asarray(absmean, np.float64).copy()
            self._counts[key] = float(count)

    def gram(self, key: str, fallback: Optional[str] = None, min_count: int = 0) -> np.ndarray:
        if key in self._grams and self._counts[key] >= min_count:
            return self._grams[key]
        if fallback is not None and fallback in self._grams:
            return self._grams[fallback]
        raise KeyError(f"no Gram for {key!r} (fallback={fallback!r})")

    def absmean(self, key: str, fallback: Optional[str] = None, min_count: int = 0) -> np.ndarray:
        # Same fallback decision as gram(): a whitening Gram and its absmean
        # must come from the SAME statistics, otherwise the stacked path
        # whitens with the layer Gram while scaling with a per-expert mean.
        if key in self._absmean and self._counts[key] >= min_count:
            k = key
        elif fallback is not None and fallback in self._absmean:
            k = fallback
        else:
            raise KeyError(f"no absmean for {key!r} (fallback={fallback!r})")
        c = max(self._counts[k], 1.0)
        return self._absmean[k] / c

    def count(self, key: str) -> float:
        return self._counts.get(key, 0.0)

    def resolve(
        self, key: str, fallback: Optional[str] = None, min_count: int = 0
    ) -> Tuple[str, Optional[str]]:
        """Which key ``gram()``/``absmean()`` would actually read, plus the
        fallback reason (None when the primary key is used, else
        "missing" or "min_count").  Pure lookup — telemetry uses it to
        count fallback usage without duplicating the decision logic."""
        if key in self._grams:
            if self._counts[key] >= min_count:
                return key, None
            reason = "min_count"
        else:
            reason = "missing"
        if fallback is not None and fallback in self._grams:
            return fallback, reason
        raise KeyError(f"no Gram for {key!r} (fallback={fallback!r})")

    def keys(self):
        return self._grams.keys()

    def save(self, path: str):
        np.savez_compressed(
            path,
            __schema__=np.asarray(GRAM_STORE_SCHEMA),
            **{f"g::{k}": v for k, v in self._grams.items()},
            **{f"a::{k}": v for k, v in self._absmean.items()},
            **{f"c::{k}": np.asarray(v) for k, v in self._counts.items()},
        )

    @classmethod
    def load(cls, path: str) -> "GramStore":
        store = cls()
        data = np.load(path)
        # Unstamped files are the legacy schema-1 layout (same arrays, no
        # version key) and migrate transparently; anything newer than this
        # build understands is rejected instead of misread.
        schema = int(data["__schema__"]) if "__schema__" in data.files else 1
        if not 1 <= schema <= GRAM_STORE_SCHEMA:
            raise ValueError(
                f"GramStore file {path!r} has schema {schema}; this build "
                f"reads schemas 1..{GRAM_STORE_SCHEMA} — refusing to "
                "misinterpret the arrays")
        names = {k[3:] for k in data.files if k.startswith("g::")}
        for name in names:
            if f"a::{name}" not in data.files or f"c::{name}" not in data.files:
                raise ValueError(
                    f"GramStore file {path!r} is corrupt: key {name!r} is "
                    "missing its absmean/count arrays")
            gram = np.asarray(data[f"g::{name}"])
            absmean = np.asarray(data[f"a::{name}"])
            if gram.ndim != 2 or gram.shape[0] != gram.shape[1] \
                    or absmean.shape != gram.shape[:1]:
                raise ValueError(
                    f"GramStore file {path!r} is corrupt: key {name!r} has "
                    f"gram {gram.shape} / absmean {absmean.shape}")
            store._grams[name] = gram
            store._absmean[name] = absmean
            store._counts[name] = float(data[f"c::{name}"])
        return store


def _get_subtree(tree: MutableMapping, path: Tuple[str, ...]):
    node = tree
    for p in path:
        node = node[p]
    return node


def _set_subtree(tree: MutableMapping, path: Tuple[str, ...], value):
    node = tree
    for p in path[:-1]:
        node = node[p]
    node[path[-1]] = value


def compress_matrix(
    kernel: np.ndarray,
    rank: int,
    config: CompressionConfig,
    gram: Optional[np.ndarray],
    absmean: Optional[np.ndarray],
    telemetry: Optional[Any] = None,
    target: str = "",
    slice_idx: Tuple[int, ...] = (),
) -> Dict[str, Any]:
    """Compress one (in, out) kernel -> factored params dict (numpy).

    ``telemetry`` (a ``repro.obs.compression.CompressionTelemetry``, duck-
    typed so core never imports obs) is a pure observer: when enabled it
    records per-slice decomposition diagnostics computed AFTER the factors
    exist, so the factored params are bit-identical with it on or off."""
    a = np.asarray(kernel, np.float64).T  # paper orientation (out, in)
    factors = nested_compress(
        a,
        rank,
        config.method,
        gram=gram,
        absmean=absmean,
        k1_frac=config.k1_frac,
        damp=config.damp,
        use_randomized=config.use_randomized,
    )
    if telemetry is not None and telemetry.enabled:
        telemetry.on_slice(
            target, slice_idx,
            decomposition_diagnostics(
                a, factors, gram=gram,
                compare_plain=getattr(telemetry, "compare_plain", True),
                use_randomized=config.use_randomized,
            ),
        )
    return factors_to_params(factors, dtype=getattr(jnp, config.dtype))


def compress_params(
    params: Mapping[str, Any],
    plan: CompressionPlan,
    grams: GramStore,
    telemetry: Optional[Any] = None,
) -> Dict[str, Any]:
    """Produce a new param pytree with every planned target factored.

    Non-target leaves are passed through by reference.  Stacked kernels
    (L, in, out) are compressed slice-by-slice against f"{gram_key}/{i}".

    ``telemetry`` (``repro.obs.compression.CompressionTelemetry``) observes
    the pass without affecting it: one ``DecompositionReport`` per target
    (plain vs whitened error, tail mass, k1/k2, outlier absorption,
    achieved-vs-requested rank/bytes, Gram fallback usage).  Compressed
    params are bit-identical with telemetry on or off.
    """
    # Fresh dicts over the same leaves: targets are replaced, never
    # mutated, so the caller's tree (and its device buffers) stays intact
    # without a copy.
    new_params = _to_mutable(params)
    cfg = plan.config
    needs_gram = cfg.method not in ("svd", "plain")
    observing = telemetry is not None and telemetry.enabled
    for spec in plan.targets:
        t0 = time.time()
        leaf = _get_subtree(new_params, spec.path)
        if "kernel" not in leaf:
            raise KeyError(f"target {spec.name} has no dense kernel (already compressed?)")
        kernel = np.asarray(leaf["kernel"], np.float32)
        rank = plan.rank_of(spec)
        fallback_slices = 0
        if spec.stacked:
            flat = kernel.reshape(-1, spec.in_dim, spec.out_dim)
            outs = []
            for flat_i, idx in enumerate(np.ndindex(*spec.stacked)):
                g = a = None
                if needs_gram:
                    suffix = "/".join(str(i) for i in idx)
                    key = (
                        f"{spec.gram_key}/{suffix}"
                        if spec.per_layer_gram
                        else spec.gram_key
                    )
                    min_count = spec.in_dim // 4
                    g = grams.gram(key, fallback=spec.gram_key, min_count=min_count)
                    a = grams.absmean(key, fallback=spec.gram_key, min_count=min_count)
                    if observing:
                        _, reason = grams.resolve(
                            key, fallback=spec.gram_key, min_count=min_count)
                        if reason is not None:
                            fallback_slices += 1
                            telemetry.on_gram_fallback(
                                key, spec.gram_key, reason)
                outs.append(compress_matrix(
                    flat[flat_i], rank, cfg, g, a,
                    telemetry=telemetry, target=spec.name, slice_idx=idx))
            factored = {
                k: jnp.stack([o[k] for o in outs]).reshape(
                    *spec.stacked, *outs[0][k].shape
                )
                for k in outs[0]
            }
        else:
            g = a = None
            if needs_gram:
                g = grams.gram(spec.gram_key)
                a = grams.absmean(spec.gram_key)
            factored = compress_matrix(kernel, rank, cfg, g, a,
                                       telemetry=telemetry, target=spec.name)
        _set_subtree(new_params, spec.path, factored)
        dt = time.time() - t0
        if observing:
            m, n = spec.out_dim, spec.in_dim
            dense_params = m * n * spec.count
            factored_params = spec.count * (m + n) * rank
            telemetry.on_target(
                name=spec.name, method=cfg.method, shape=(m, n),
                stacked=spec.stacked, rank=rank,
                requested_rank=rank_for_ratio(m, n, cfg.ratio),
                requested_ratio=cfg.ratio,
                achieved_ratio=1.0 - factored_params / dense_params,
                dense_params=dense_params, factored_params=factored_params,
                gram_fallback_slices=fallback_slices, seconds=dt)
        logger.info("compressed %s rank=%d in %.2fs", spec.name, rank, dt)
    return new_params


def _to_mutable(tree):
    if isinstance(tree, Mapping):
        return {k: _to_mutable(v) for k, v in tree.items()}
    return tree


def compress_model(
    params: Mapping[str, Any],
    targets,
    grams: GramStore,
    config: CompressionConfig,
    telemetry: Optional[Any] = None,
) -> Tuple[Dict[str, Any], CompressionPlan]:
    """Plan + execute in one call (the public API used by examples)."""
    plan = build_plan(targets, config)
    return compress_params(params, plan, grams, telemetry=telemetry), plan
