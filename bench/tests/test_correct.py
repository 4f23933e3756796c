"""How ``correct`` is decided, at a size a CPU test run holds: the tiny
configuration (a bfloat16 Llama-style decoder at small widths) through the
whole run path, the chip check skipped.

The limit in ``data/tiny.json`` lies between the two readings, as a
cell's does: over 24 runs of 5 s (12 seeds, both mixes) with at least 150
served tokens compared, sound runs read a widest served-token gap of
0.002-0.050 and the int8 control, at the same served positions,
0.048-0.352 (23 of 24 above the limit 0.06).  At these widths the two lie
closer together than at a cell's; the seeds below read the control at
0.085 or more."""

import time

import jax
import pytest

from conftest import tiny_mix
from harness import cell_run, spec


def _cell(config, mix_name):
    return spec.Cell(f"tiny.{mix_name}", config, tiny_mix(mix_name), 1,
                     (), ())


def _run(cell, seed, fault=None):
    return cell_run.run(cell, seed, 5.0, False, time.perf_counter(),
                        jax.devices(), fault=fault)


@pytest.mark.parametrize("mix", ["decode", "docs"])
def test_a_sound_run_is_correct(tiny_config, mix):
    res = _run(_cell(tiny_config, mix), 2 ** 31 + 11)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def _alter_decoded_tokens(eng):
    """The decode root's sampled tokens, each moved to the next id: a token
    altered where it is produced."""
    decode, vocab = eng._decode, eng.model.cfg.vocab_size

    def altered(*args):
        out = decode(*args)
        return ((out[0] + 1) % vocab,) + tuple(out[1:])

    eng._decode = altered


@pytest.mark.parametrize("mix", ["decode", "docs"])
def test_a_token_altered_where_produced_is_not_correct(tiny_config, mix):
    res = _run(_cell(tiny_config, mix), 2 ** 31 + 11,
               fault=_alter_decoded_tokens)
    assert not res["correct"]
    c = res["checks"]["served_gap_max"]
    assert c["value"] > 10 * c["limit"]


@pytest.mark.parametrize("mix", ["decode", "docs"])
def test_the_control_is_not_correct(tiny_config, mix):
    """The int8 control in the served tokens' place, judged by the run's
    own verdict at the served positions, fails where the same run's served
    tokens pass."""
    for seed in (2 ** 31 + 21, 2 ** 31 + 22, 2 ** 31 + 23):
        res = cell_run.run(_cell(tiny_config, mix), seed, 5.0, False,
                           time.perf_counter(), jax.devices(), control=True)
        assert not res["correct"], res["checks"]
        limit = res["checks"]["served_gap_max"]["limit"]
        assert res["checks"]["served_gap_max"]["value"] > limit
        assert res["notes"]["served_gap_max_of_program"] <= limit
