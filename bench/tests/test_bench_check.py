"""The correctness check at a size a test run holds: a sound run of the
real harness passes it, and the control (the reference one precision
below the configuration's, judged in the served tokens' place) fails
it.  The same readings on the chip at the cells' own sizes come from
``bench/control.py``."""
import pytest

import benchtest


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.offline"])
def test_sound_run_is_correct_and_reports_its_metrics(cell):
    out, info = benchtest.run_tiny(cell, seed=2 ** 31 + 9)
    assert out["correct"] is True
    assert out["checks"]["max_logit_gap"]["value"] <= benchtest.TINY_LIMIT
    assert out["checks"]["tokens_checked"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    judged = {"tiny.chat": {"ttft_p90_ms", "tpot_p90_ms", "setup_s"},
              "tiny.offline": {"output_tok_s", "tpot_p90_ms", "setup_s"}}
    assert set(out["metrics"]) == judged[cell]
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    # every program the window drives compiled during set-up
    assert info["window_compiles"] == 0


def test_control_fails_the_limit():
    cfg = benchtest.fixture("tiny.json")
    out, info = benchtest.run_tiny("tiny.offline", seed=5,
                                   control=cfg["control"])
    rows = info["rows"]
    ctl = max(r["control"] for r in rows)
    prog = max(r["served"] for r in rows)
    assert prog <= benchtest.TINY_LIMIT < ctl
    # the control takes the served tokens' place in the run's own check
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] == ctl
