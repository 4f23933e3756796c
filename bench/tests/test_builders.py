"""A configuration file names the builder of its model
(``bench/builders/<name>.py``, ``llama`` by default), and the harness draws
that model's params and counts its work without an edit to its code."""

import json
import shutil

import jax
import jax.numpy as jnp
import pytest

from conftest import DATA
from harness import roofline, spec, weights


def _llama_as_before(config):
    """The mapping the harness applied to every configuration before
    builders were found by name, kept here as the builder's reference."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        head_dim=config["head_dim"],
        attention="gqa", pos_emb="rope", rope_theta=config["rope_theta"],
        norm="rmsnorm", activation="swiglu",
        tie_embeddings=config["tie_word_embeddings"],
        max_seq=config["max_position_embeddings"],
        dtype=config["torch_dtype"],
    )


@pytest.mark.parametrize("path", [
    spec.BENCH / "configs" / "mistral7b-nsvd.json",
    spec.BENCH / "configs" / "phi3m-nsvd.json",
    DATA / "tiny.json"], ids=lambda p: p.stem)
def test_a_file_that_names_no_builder_gets_the_llama_builder(path):
    config = json.loads(path.read_text())
    assert "builder" not in config
    assert spec.model_config(config) == _llama_as_before(config)


@pytest.fixture
def moe(bench_copy):
    """The test MoE builder installed as ``bench/builders/tiny_moe.py``:
    (model, configuration) of ``data/tiny_moe.json`` (8 experts, top 2,
    1 shared expert)."""
    from repro.models import build_model

    shutil.copy(DATA / "tiny_moe.py",
                bench_copy / "bench" / "builders" / "tiny_moe.py")
    config = json.loads((DATA / "tiny_moe.json").read_text())
    return build_model(spec.model_config(config)), config


def test_a_named_builder_is_found_by_name(moe):
    model, _ = moe
    assert model.cfg.family == "moe"
    assert (model.cfg.moe.num_experts, model.cfg.moe.top_k,
            model.cfg.moe.num_shared_experts) == (8, 2, 1)


def test_each_leaf_keeps_the_dtype_the_model_gives_it(moe):
    model, config = moe
    params = weights.build_params(model, config["compression"], 2 ** 33 + 5)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    routers = [x for p, x in leaves if "router" in jax.tree_util.keystr(p)]
    assert routers and all(x.dtype == jnp.float32 for x in routers)
    rest = [x for p, x in leaves if "router" not in jax.tree_util.keystr(p)]
    assert all(x.dtype == jnp.bfloat16 for x in rest)


@pytest.mark.parametrize("published, routed", [(None, 2), (32, 0.5)])
def test_a_token_runs_its_top_k_experts_of_those_published(moe, published,
                                                          routed):
    """Of 8 experts held, a token runs 2 (top 2 of the 8 published), or,
    where 32 are published and the other 24 lie on other chips, 2 x 8/32
    here."""
    model, config = moe
    rows = weights.factored_rows(model, config["compression"])
    ranks, _ = weights.rank_plan(model, config["compression"])

    def rank(*path):
        k1, k2 = ranks[("g0", "sub0") + path]
        return k1 + k2

    d, hq, hkv, f = 128, 4 * 32, 2 * 32, 96
    attn = ((d + hq) * (rank("attn", "wq") + rank("attn", "wo"))
            + (d + hkv) * (rank("attn", "wk") + rank("attn", "wv")))
    ffn = {kind: (d + f) * (rank("moe", kind, "wi") + rank("moe", kind, "wg"))
           + (f + d) * rank("moe", kind, "wo")
           for kind in ("experts", "shared")}
    layers = 2
    by_hand = 2 * layers * (attn + ffn["shared"] + routed * ffn["experts"])
    assert [r.experts for r in rows] == ["experts" in r.path for r in rows]
    assert sum(r.experts for r in rows) == 3
    assert roofline.linear_flops_per_token(rows, 2, published) == by_hand
