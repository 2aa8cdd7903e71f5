"""The ``deepseek-v2-lite.chat-2k`` cell end to end at its rehearsal size
on the CPU: a sound run comes out correct, with no (token, expert) pair
dropped and nothing compiled in the window; a planted fault and the
control one precision below the configuration's come out not correct."""
import pytest

from bench.tests.test_rehearsal import run_cell

CELL = "deepseek-v2-lite.chat-2k"


def test_sound_run_is_correct(tmp_path):
    res = run_cell(CELL, tmp_path)
    assert res["correct"], res["checks"]
    assert res["metrics"] == {}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["window_compiles"]["value"] == 0
    assert res["checks"]["moe_pairs_dropped"]["value"] == 0
    assert res["checks"]["wrong_length"]["value"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_fault_is_caught(fault, tmp_path):
    """A tick that hands its plan state back unchanged (no generated
    latent row ever lands), or one served token altered."""
    res = run_cell(CELL, tmp_path, "--fault", fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_control_is_caught(tmp_path):
    """The float8 control fails ``logit_gap`` at the rehearsal size too."""
    res = run_cell(CELL, tmp_path, "--control", "fp8")
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
