"""The harness end to end on the CPU at a tiny size: it refuses to measure
without a chip, and with the timed path broken underneath, the comparison
with the plain reference comes out not correct."""

import subprocess
import sys
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import MOE, PEAKS, REPO

from perfbench import discover, faults, harness
from perfbench.check_control import control_picks

# limit for the tiny configuration (CPU, about 200 served tokens checked),
# from the weights drawn per leaf and layer: sound runs of decode_batch read
# wide_gap_share 0.053-0.122 (seeds 11-20; seed 11, which these tests run,
# 0.099-0.122 over ten runs, as the window ends at another request), chat
# 0.000-0.017 and the MoE family 0.006-0.017 (seeds 11-12); the control
# 0.148-0.283 (seed 11: 0.232), a stale cache 0.33-0.38, half a batch
# 0.37-0.38, an altered token 1.0
TINY_LIMITS = {"wide_gap_share": 0.15}


def run(root, cell, seed=11, seconds=1):
    return harness.run_cell(cell, seed, seconds, False, repo=root, require_chip=False,
                            peaks=PEAKS, log=lambda s: None)


def test_refuses_a_cpu():
    with pytest.raises(discover.BenchError, match="no TPU"):
        harness.run_cell("h2o_danube_1_8b.chat", 1, 1, False, log=lambda s: None)
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload", "h2o_danube_1_8b.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=REPO,
                       capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu", "PATH": ""},
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("traffic", ["chat", "decode_batch"])
def test_sound_run_is_correct(checkout, traffic):
    cfg = "h2o_danube_1_8b" if traffic == "chat" else "nemotron_4_15b"
    root, cell = checkout(cfg_name=cfg, traffic=traffic, limits=TINY_LIMITS)
    line = run(root, cell)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    names = set(line["metrics"])
    assert "setup_s" in names and "itl_p95_ms" in names and "hbm_in_use_gib" in names
    assert ("ttft_p90_s" in names) == (traffic == "chat")
    assert line["failed"] == 0 and line["attempted"] >= 3


def test_moe_sound_run_is_correct(checkout):
    """The program's mixture-of-experts family, built a block at a time and
    checked against the layer-streamed MoE reference."""
    root, cell = checkout(cfg_name="h2o_danube_1_8b", traffic="chat", limits=TINY_LIMITS,
                          size=MOE)
    line = run(root, cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 3


def test_control_comes_out_not_correct(checkout):
    """The configuration's control (one bit lower: W3A3, W4 down-projection)
    in the program's place, over the prompts and tokens a sound run served."""
    root, cell = checkout(cfg_name="nemotron_4_15b", traffic="decode_batch",
                          limits=TINY_LIMITS)
    keep = {}
    line = harness.run_cell(cell, 11, 1, False, repo=root, require_chip=False, peaks=PEAKS,
                            log=lambda s: None, keep=keep)
    assert line["correct"], line["checks"]
    serve = discover.traffic("tiny_mix", root / "perfbench")["serve"]
    picks = control_picks(keep["cfg"], 11, keep["seqs"], keep["starts"], serve)
    ctl = harness.reference_gaps(keep["cfg"], 11, keep["seqs"], keep["starts"], root, picks=picks)
    assert not harness.within(ctl, TINY_LIMITS), ctl


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_faults_come_out_not_correct(checkout, fault):
    root, cell = checkout(cfg_name="nemotron_4_15b", traffic="decode_batch",
                          limits=TINY_LIMITS)
    with faults.planted(fault):
        line = run(root, cell)
    assert not line["correct"], line["checks"]


class Telemetry:
    def first_token(self, rid):
        pass

    def tokens_committed(self, rid, n):
        pass

    def request_event(self, rid, name, **args):
        pass

    def request_admitted(self, rid, prefix_hit_tokens=0):
        pass


def test_tokens_the_hooks_missed_refuse_the_run():
    tel = Telemetry()
    drv = harness.Client(NS(scheduler=NS(), telemetry=tel), [], {"loop": "open"}, print)
    drv.rid_of = {7: 0}
    tel.first_token(7)
    tel.tokens_committed(7, 1)
    drv.tokens.stamp(1.0)
    drv.results = {7: [5, 6]}
    drv.check_stamps()
    drv.results = {7: [5, 6, 8]}  # a token that no telemetry call reported
    with pytest.raises(discover.BenchError, match="reported 2"):
        drv.check_stamps()


@pytest.mark.parametrize("hook", ["tokens_committed", "_packed_fn"])
def test_missing_hooks_refuse_the_run(hook):
    with pytest.raises(discover.BenchError, match=hook):
        if hook == "_packed_fn":
            harness.StepRecorder(NS())
        else:
            tel = Telemetry()
            tel.tokens_committed = None
            harness.TokenLog(tel)
