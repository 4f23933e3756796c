"""Launch-layer tests: input specs, HLO collective parsing, sharding rules,
rank budgeting for deployment plans."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPE_CASES, applicable_shapes, get_config
from repro.configs.registry import ASSIGNED
from repro.launch.hlo_stats import collective_stats
from repro.models.api import input_specs
from repro.parallel.sharding import param_pspec


class TestInputSpecs:
    @pytest.mark.parametrize("arch", sorted(ASSIGNED))
    def test_all_cells_have_specs(self, arch):
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            case = SHAPE_CASES[shape]
            specs = input_specs(cfg, case)
            assert "tokens" in specs
            if case.kind == "decode":
                assert specs["tokens"].shape == (case.global_batch, 1)
                assert specs["cache_len"].shape == (case.global_batch,)
            else:
                total = specs["tokens"].shape[1]
                if cfg.frontend == "vision":
                    total += cfg.num_patches
                assert total == case.seq_len
                assert specs["tokens"].shape[0] == case.global_batch

    def test_modality_stubs(self):
        w = input_specs(get_config("whisper-small"), SHAPE_CASES["train_4k"])
        assert w["frames"].shape == (256, 1500, 768)
        l = input_specs(get_config("llava-next-mistral-7b"), SHAPE_CASES["train_4k"])
        assert l["patches"].shape == (256, 576, 1024)


class TestHLOStats:
    def test_parses_collectives_with_trip_counts(self):
        hlo = """
HloModule test

%body (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), replica_groups=[4,8]<=[32], to_apply=%add
  ROOT %t = tuple(...)
}

%cond (p: (s32[], f32[128])) -> pred[] {
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %c), direction=LT
}

ENTRY %main () -> f32[128] {
  %w = while(...), condition=%cond, body=%body
  %ag = f32[256]{0} all-gather(f32[128]{0} %y), replica_groups={{0,1}}, dimensions={0}
  ROOT %r = f32[128] get-tuple-element(%w), index=1
}
"""
        stats = collective_stats(hlo)
        # all-reduce inside the while: counted 12x, group size 8.
        assert stats["all-reduce"]["count"] == 12
        expected_ar = 12 * 2 * 128 * 4 * (8 - 1) / 8
        assert abs(stats["all-reduce"]["wire_bytes"] - expected_ar) < 1e-6
        # all-gather at entry: counted once, group size 2.
        assert stats["all-gather"]["count"] == 1
        assert stats["all-gather"]["bytes"] == 256 * 4


class TestShardingRules:
    def test_attention_tp(self):
        leaf = jax.ShapeDtypeStruct((512, 2048), jnp.bfloat16)
        assert param_pspec(("g0", "sub0", "attn", "wq", "kernel"), leaf) == P(None, "model")
        assert param_pspec(("g0", "sub0", "attn", "wo", "kernel"), leaf) == P("model", None)

    def test_factored_input_output_sharding(self):
        """u shards its input dim, v its output dim — NEVER replicated
        (boundary inheritance replicated u for column-parallel layers;
        measured 2.7x dense bytes — §Perf C1)."""
        u = jax.ShapeDtypeStruct((2048, 128), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((128, 512), jnp.bfloat16)
        assert param_pspec(("mlp", "wo", "u"), u) == P("model", None)
        assert param_pspec(("mlp", "wo", "v"), v) == P(None, "model")
        assert param_pspec(("mlp", "wi", "u"), u) == P("model", None)
        assert param_pspec(("mlp", "wi", "v"), v) == P(None, "model")
        # Tiny replicated linears stay replicated when factored.
        assert param_pspec(("attn", "wkv_a", "u"), u) == P(None, None)

    def test_experts_ep(self):
        leaf = jax.ShapeDtypeStruct((64, 2048, 1408), jnp.bfloat16)
        spec = param_pspec(("g1", "sub0", "moe", "experts", "wi", "kernel"), leaf)
        assert spec == P("model", None, None)

    def test_stacked_prefix_nones(self):
        leaf = jax.ShapeDtypeStruct((47, 64, 2048, 1408), jnp.bfloat16)
        spec = param_pspec(("g1", "sub0", "moe", "experts", "wi", "kernel"), leaf)
        assert spec == P(None, "model", None, None)

    def test_fsdp_adds_dp_axis(self):
        leaf = jax.ShapeDtypeStruct((8192, 22016), jnp.bfloat16)
        spec = param_pspec(("mlp", "wi", "kernel"), leaf, fsdp_axes=("data",))
        assert spec == P("data", "model")

    def test_rwkv_rules(self):
        leaf = jax.ShapeDtypeStruct((2048, 7168), jnp.bfloat16)
        assert param_pspec(("rwkv_c", "wk", "kernel"), leaf) == P(None, "model")
        leaf2 = jax.ShapeDtypeStruct((7168, 2048), jnp.bfloat16)
        assert param_pspec(("rwkv_c", "wv", "kernel"), leaf2) == P("model", None)


class TestRankBudget:
    def test_mxu_aligned_ranks(self):
        from repro.core import rank_for_ratio

        k = rank_for_ratio(8192, 22016, 0.3, multiple_of=128)
        assert k % 128 == 0
        assert (8192 + 22016) * k <= 0.7 * 8192 * 22016

    def test_compressed_shapes_plan(self):
        from repro.launch.compress_shapes import compressed_param_shapes
        from repro.models import build_model, param_specs

        cfg = get_config("chatglm3-6b")
        model = build_model(cfg)
        shapes = param_specs(cfg)
        cshapes = compressed_param_shapes(model, shapes, 0.3)
        import numpy as np

        dense = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        comp = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(cshapes))
        assert comp < 0.78 * dense  # ~30% removed from the compressible set


class TestServeCLI:
    def test_reduced_is_a_switch_and_layers_cut_depth_only(self):
        from repro.launch.serve import build_parser, model_config

        ap = build_parser()
        assert model_config(ap.parse_args(["--arch", "mistral-7b"])) == \
            get_config("mistral-7b").reduced()
        full = get_config("mistral-7b")
        cfg = model_config(ap.parse_args(
            ["--arch", "mistral-7b", "--no-reduced", "--layers", "2"]))
        assert cfg.num_layers == 2
        assert cfg == full.__class__(**{**full.__dict__, "num_layers": 2})

    def test_serve_returns_engine_outputs_and_phase_seconds(self, tmp_path,
                                                            monkeypatch):
        """The CLI path in-process: compress into the model's own dtype,
        serve, hand back what was served."""
        from jax.experimental.compilation_cache import compilation_cache

        from repro.launch.serve import build_parser, serve

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        was = jax.config.jax_compilation_cache_dir
        try:
            served = serve(build_parser().parse_args(
                ["--arch", "mistral-7b", "--layers", "1", "--requests", "2",
                 "--prompt-len", "5", "--max-new", "3", "--max-len", "32",
                 "--compress", "0.5"]))
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
            compilation_cache.reset_cache()
        assert sorted(len(v) for v in served.outputs.values()) == [3, 3]
        assert served.cfg.num_layers == 1
        assert {"calibrate", "compress", "serve"} <= set(served.seconds)
        wq = served.params["g0"]["sub0"]["attn"]["wq"]
        assert {"u", "v"} <= set(wq) and "kernel" not in wq
        assert wq["u"].dtype == jnp.dtype(served.cfg.dtype)


class TestCompilationCache:
    @pytest.mark.parametrize("from_env", [True, False],
                             ids=["env", "in-checkout"])
    def test_cache_dir(self, tmp_path, monkeypatch, from_env):
        from jax.experimental.compilation_cache import compilation_cache

        from repro.launch.compile_cache import (
            DEFAULT_DIR,
            ENV_VAR,
            enable_compilation_cache,
        )

        if from_env:
            monkeypatch.setenv(ENV_VAR, str(tmp_path))
        else:
            monkeypatch.delenv(ENV_VAR, raising=False)
        was = jax.config.jax_compilation_cache_dir
        try:
            got = enable_compilation_cache()
            assert got == jax.config.jax_compilation_cache_dir
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
            compilation_cache.reset_cache()
        want = str(tmp_path) if from_env else str(DEFAULT_DIR)
        assert got == want
        # A fixed directory at the root of the checkout.
        assert DEFAULT_DIR.name == ".jax_cache"
        assert (DEFAULT_DIR.parent / "src" / "repro").is_dir()


class TestDevicePeaks:
    def test_v5e_peaks_by_device_kind(self):
        from repro.launch.mesh import device_peaks

        p = device_peaks("TPU v5 lite")
        assert p["flops_bf16"] == 197e12 and p["hbm_bw"] == 819e9

    def test_unknown_kind_raises(self):
        from repro.launch.mesh import device_peaks

        with pytest.raises(KeyError, match="no published peaks"):
            device_peaks("cpu")


class TestGramCache:
    def test_key_tracks_depth_widths_and_vocab(self):
        import dataclasses

        from benchmarks.common import gram_cache_key

        cfg = get_config("mistral-7b")
        keys = {gram_cache_key(c) for c in (
            cfg, dataclasses.replace(cfg, num_layers=2),
            dataclasses.replace(cfg, d_ff=1024),
            dataclasses.replace(cfg, vocab_size=512))}
        assert len(keys) == 4

    def test_calibration_draws_from_the_model_vocabulary(self, monkeypatch):
        import benchmarks.common as common
        from repro.models import build_model

        cfg = get_config("mistral-7b").reduced()
        seen = []

        def fake_collect(model, params, batches):
            seen.extend(int(b["tokens"].max()) for b in batches)
            return "store"

        monkeypatch.setattr(common, "collect_grams", fake_collect)
        assert common.get_grams(build_model(cfg), None, n_samples=64,
                                cache=False) == "store"
        assert seen and max(seen) < cfg.vocab_size
