"""Paper Table 4: NID-I (interpolative-decomposition residual step) at 30%
compression, k1 in {0.99, 0.95, 0.90}.

Expected qualitative reproduction: NID helps in-domain with tiny k2
(k1=0.99) but is weaker than NSVD out-of-domain (the paper's observation
that the CMRC column degrades under NID).
"""

from __future__ import annotations

import time

from .common import compress_and_eval, fmt_row, get_grams, load_table, save_table, train_small_lm

K1_FRACS = (0.99, 0.95, 0.90)
RATIO = 0.3


def run(model_name: str = "small-llama"):
    cached = load_table("table4_nid")
    if cached:
        for r in cached:
            print(fmt_row(f"{r['method']} k1={r['k1_frac']:.2f}", r))
        return cached
    model, params, _ = train_small_lm(model_name)
    grams = get_grams(model, params)
    rows = []
    base = compress_and_eval(model, params, grams, "asvd1", RATIO)
    rows.append({"k1_frac": 1.0, "method": "asvd1", **base})
    print(fmt_row("asvd1 (baseline)", base))
    for k1 in K1_FRACS:
        ppls = compress_and_eval(model, params, grams, "nid1", RATIO, k1_frac=k1)
        rows.append({"k1_frac": k1, "method": "nid1", **ppls})
        print(fmt_row(f"nid1 k1={k1:.2f}", ppls))
    save_table("table4_nid", rows)
    return rows


def main():
    t0 = time.time()
    rows = run()
    # Derived: in-domain (en_b) improvement at k1=0.99 vs asvd1.
    d = (rows[0]["en_b"] - rows[1]["en_b"]) / rows[0]["en_b"]
    print(f"table4_nid,{(time.time()-t0)*1e6:.0f},{d:.4f}")
    return rows


if __name__ == "__main__":
    main()
