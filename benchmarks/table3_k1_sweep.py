"""Paper Table 3: NSVD-I at 30% compression with k1 in
{0.99, 0.95, 0.90, 0.85, 0.80}k.

Expected qualitative reproduction: smaller k1 (larger residual budget k2)
helps MORE on the shifted domains (zh/jp) and costs a little on the
calibration domain — the paper's k1 trade-off direction.
"""

from __future__ import annotations

import time

from .common import (
    compress_and_eval,
    load_table,
    fmt_row,
    get_grams,
    save_table,
    train_small_lm,
)

K1_FRACS = (1.0, 0.99, 0.95, 0.90, 0.85, 0.80)
RATIO = 0.3


def run(model_name: str = "small-llama"):
    cached = load_table("table3_k1_sweep")
    if cached:
        for r in cached:
            print(fmt_row(f"k1={r['k1_frac']:.2f} ({r['method']})", r))
        return cached
    model, params, _ = train_small_lm(model_name)
    grams = get_grams(model, params)
    rows = []
    for k1 in K1_FRACS:
        method = "asvd1" if k1 == 1.0 else "nsvd1"
        ppls = compress_and_eval(model, params, grams, method, RATIO, k1_frac=k1)
        rows.append({"k1_frac": k1, "method": method, **ppls})
        print(fmt_row(f"k1={k1:.2f} ({method})", ppls))
    save_table("table3_k1_sweep", rows)
    return rows


def main():
    t0 = time.time()
    rows = run()
    # Derived: OOD improvement of k1=0.8 over the asvd baseline (zh+jp).
    base = rows[0]
    k80 = rows[-1]
    ood = sum((base[d] - k80[d]) / base[d] for d in ("zh", "jp")) / 2
    print(f"table3_k1_sweep,{(time.time()-t0)*1e6:.0f},{ood:.4f}")
    return rows


if __name__ == "__main__":
    main()
