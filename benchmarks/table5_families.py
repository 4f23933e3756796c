"""Paper Tables 5 & 6: NSVD vs ASVD across model FAMILIES (llama-like,
opt-like w/ LayerNorm+GELU+learned-pos, mistral-like w/ GQA) and across
SCALES (small-llama vs small-llama-13b) at 30% compression.

Expected qualitative reproduction: NSVD-I beats ASVD-0/I on every family,
with family-dependent margins (paper: +27.6% vicuna, +4.4% mistral, +30.1%
opt) and a shrinking margin at larger scale (paper Table 6).
"""

from __future__ import annotations

import time

from .common import (
    EVAL_DOMAINS,
    compress_and_eval,
    load_table,
    fmt_row,
    get_grams,
    save_table,
    train_small_lm,
)

FAMILIES = ("small-llama", "small-opt", "small-mistral", "small-llama-13b")
RATIO = 0.3
METHODS = ("asvd0", "asvd1", "nsvd1")


def run():
    cached = load_table("table5_families")
    if cached:
        for r in cached:
            print(fmt_row(f"{r['model']} {r['method']}", r))
        return cached
    rows = []
    for name in FAMILIES:
        model, params, _ = train_small_lm(name)
        grams = get_grams(model, params)
        for method in METHODS:
            ppls = compress_and_eval(model, params, grams, method, RATIO)
            rows.append({"model": name, "method": method, **ppls})
            print(fmt_row(f"{name} {method}", ppls))
    save_table("table5_families", rows)
    return rows


def avg_improvement(rows, model_name: str) -> float:
    doms = [d for d in EVAL_DOMAINS if d != "en_a"]
    nsvd = next(r for r in rows if r["model"] == model_name and r["method"] == "nsvd1")
    best_base = {
        d: min(
            r[d] for r in rows
            if r["model"] == model_name and r["method"] in ("asvd0", "asvd1")
        )
        for d in doms
    }
    return sum((best_base[d] - nsvd[d]) / best_base[d] for d in doms) / len(doms)


def main():
    t0 = time.time()
    rows = run()
    worst = min(avg_improvement(rows, f) for f in FAMILIES)
    print(f"table5_families,{(time.time()-t0)*1e6:.0f},{worst:.4f}")
    return rows


if __name__ == "__main__":
    main()
