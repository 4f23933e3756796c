"""Quickstart: NSVD-compress a small LM and compare perplexity.

    PYTHONPATH=src:. python examples/quickstart.py

Walks the full public API: build model -> train briefly -> collect
calibration Grams -> build compression plan -> compress -> evaluate on the
calibration domain and two distribution-shifted domains.
"""

import sys

sys.path.insert(0, "src")
sys.path.insert(0, ".")


from benchmarks.common import (
    VOCAB,
    baseline_ppl,
    get_grams,
    train_small_lm,
)
from repro.core import CompressionConfig, build_plan, compress_params
from repro.eval.perplexity import eval_batches, evaluate_ppl


def main():
    print("1) train (or load) a small llama-family LM ...")
    model, params, extra = train_small_lm("small-llama", steps=300)

    print("2) collect calibration Grams on the en_a domain (256 samples) ...")
    grams = get_grams(model, params)

    print("3) plan NSVD-I compression at 30% parameter removal ...")
    cfg = CompressionConfig(method="nsvd1", ratio=0.3, k1_frac=0.9,
                            dtype="float32", use_randomized=False)
    plan = build_plan(model.compressible_targets(), cfg)
    print(plan.summary())

    print("4) compress ...")
    cparams = compress_params(params, plan, grams)

    print("5) evaluate ...")
    base = baseline_ppl(model, params, domains=("en_a", "en_b", "jp"))
    for d in ("en_a", "en_b", "jp"):
        ppl = evaluate_ppl(model, cparams, eval_batches(VOCAB, d, n_batches=4))
        print(f"   {d:<5} dense={base[d]:8.2f}  nsvd-30%={ppl:8.2f}")


if __name__ == "__main__":
    main()
