"""Paper Table 1: PPL of the compressed LM across compression ratios
10-50% for SVD / ASVD-0 / ASVD-I / ASVD-II / NSVD-I / NSVD-II.

Calibration domain: en_a (WikiText-2 stand-in).  Eval domains include the
distribution-shifted zh / jp stand-ins (CMRC / AlpacaEval-JP analogues).
Expected qualitative reproduction: NSVD ~= ASVD on en_a, and increasingly
better out-of-domain as the ratio grows (paper: -14.7% avg PPL at 30%).
"""

from __future__ import annotations

import time
from typing import List

from .common import (
    EVAL_DOMAINS,
    baseline_ppl,
    compress_and_eval,
    fmt_row,
    get_grams,
    load_table,
    save_table,
    train_small_lm,
)

RATIOS = (0.1, 0.2, 0.3, 0.4, 0.5)
METHODS = ("svd", "asvd0", "asvd1", "asvd2", "nsvd1", "nsvd2")


def run(model_name: str = "small-llama", ratios=RATIOS, methods=METHODS):
    cached = load_table(f"table1_{model_name}")
    if cached:
        for r in cached:
            print(fmt_row(f"r={r['ratio']:.0%} {r['method']}", r))
        return cached
    model, params, _ = train_small_lm(model_name)
    grams = get_grams(model, params)
    rows: List[dict] = []
    base = baseline_ppl(model, params)
    print(fmt_row("original", base))
    rows.append({"ratio": 0.0, "method": "original", **base})
    for ratio in ratios:
        for method in methods:
            ppls = compress_and_eval(model, params, grams, method, ratio)
            rows.append({"ratio": ratio, "method": method, **ppls})
            print(fmt_row(f"r={ratio:.0%} {method}", ppls))
    save_table(f"table1_{model_name}", rows, {"model": model_name})
    return rows


def derived_improvement(rows, ratio: float, nested="nsvd1", base="asvd1") -> float:
    """Avg relative PPL improvement of nested vs best ASVD baseline over the
    shifted domains (paper's Avg. Impro. column, excluding calibration)."""
    doms = [d for d in EVAL_DOMAINS if d != "en_a"]
    r_n = next(r for r in rows if r["ratio"] == ratio and r["method"] == nested)
    r_b = next(r for r in rows if r["ratio"] == ratio and r["method"] == base)
    rels = [(r_b[d] - r_n[d]) / r_b[d] for d in doms]
    return sum(rels) / len(rels)


def main():
    t0 = time.time()
    rows = run()
    impro30 = derived_improvement(rows, 0.3)
    print(f"table1_ratio_sweep,{(time.time()-t0)*1e6:.0f},{impro30:.4f}")
    return rows


if __name__ == "__main__":
    main()
