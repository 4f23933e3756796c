"""Streaming Gram accumulation from model activation taps.

For each tapped activation x (.., n) we accumulate
    G += sum over rows of x^T x      (n, n) fp32 on device, fp64 on host
    a += sum |x|                      (n,)
    c += row count
jitted per batch; the host store sums across batches in float64.

Tap names from unrolled scan groups look like  "g0/rep3/sub0.mlp.in";
``normalize_tap`` rewrites them to the GramStore key "g0/sub0.mlp.in/3"
that compression targets look up (plus the shared fallback key
"g0/sub0.mlp.in" accumulated over all layers).

MoE expert buffers are tapped as (E, C, D) with zero-padded slots (they
contribute nothing to the Gram); per-expert keys get "/e{idx}" suffixes.
"""

from __future__ import annotations

import re
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compress import GramStore

_REP_RE = re.compile(r"/rep(\d+)/")


def normalize_tap(name: str) -> Tuple[str, str]:
    """Returns (base_key, slice_suffix).  base_key has the rep index moved
    out; suffix is "" or "3"."""
    m = _REP_RE.search(name)
    if not m:
        return name, ""
    base = _REP_RE.sub("/", name)
    return base, m.group(1)


@jax.jit
def gram_update(x: jax.Array):
    """x: (..., n) -> (G (n,n) f32, absmean-sum (n,), count)."""
    n = x.shape[-1]
    flat = x.reshape(-1, n).astype(jnp.float32)
    g = jnp.matmul(flat.T, flat, precision=jax.lax.Precision.HIGHEST)
    a = jnp.sum(jnp.abs(flat), axis=0)
    c = jnp.asarray(flat.shape[0], jnp.float32)
    return g, a, c


@jax.jit
def expert_gram_update(buf: jax.Array):
    """buf: (E, C, n) zero-padded -> per-expert (E,n,n), (E,n), counts (E,)."""
    e, c, n = buf.shape
    b = buf.astype(jnp.float32)
    g = jnp.einsum("ecn,ecm->enm", b, b, precision=jax.lax.Precision.HIGHEST)
    a = jnp.sum(jnp.abs(b), axis=1)
    cnt = jnp.sum(jnp.any(b != 0, axis=-1), axis=1).astype(jnp.float32)
    return g, a, cnt


def accumulate_taps(
    store: GramStore,
    taps: Dict[str, jax.Array],
    telemetry=None,
) -> None:
    """Fold one batch of taps into the host GramStore.

    ``telemetry`` (``repro.obs.compression.CompressionTelemetry``) gets the
    cheap per-batch signal only — rows folded per normalized tap; the
    expensive per-tap statistics (outlier fractions, Gram condition) run
    once at the end of calibration in ``runner.collect_grams``."""
    tap_rows: Dict[str, float] = {}
    observing = telemetry is not None and telemetry.enabled
    for name, x in taps.items():
        base, suffix = normalize_tap(name)
        if base.endswith(("expert_buf", "expert_mid")):
            g, a, cnt = expert_gram_update(x)
            g = np.asarray(g, np.float64)
            a = np.asarray(a, np.float64)
            cnt = np.asarray(cnt, np.float64)
            for ei in range(g.shape[0]):
                key = f"{base}/{suffix}/{ei}" if suffix else f"{base}/{ei}"
                store.update(key, g[ei], a[ei], float(cnt[ei]))
            # Shared fallback across experts (+ layers).
            store.update(base, g.sum(0), a.sum(0), float(cnt.sum()))
            if observing:
                tap_rows[base] = tap_rows.get(base, 0.0) + float(cnt.sum())
        else:
            g, a, c = gram_update(x)
            # float32 from the device; the store sums in float64.
            g, a = np.asarray(g), np.asarray(a)
            if suffix:
                store.update(f"{base}/{suffix}", g, a, float(c))
            store.update(base, g, a, float(c))
            if observing:
                tap_rows[base] = tap_rows.get(base, 0.0) + float(c)
    if observing:
        telemetry.on_calib_batch(tap_rows)
