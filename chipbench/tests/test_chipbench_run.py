"""Whole runs of tiny cells on the CPU, the look for a chip skipped:
a sound run is correct, and each fault the serving path can have,
planted under the timed path, turns ``correct`` false."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from chipbench import run
from chipbench.tests import tiny
from repro.models import lm
from repro.serve import engine as engine_mod

CELLS = ["tiny-dense.chat", "tiny-ssm.chat"]
SEED = 2**31 + 3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def go(root, workload, trace=False, seed=SEED):
    return run.run_cell(root, workload, seed, 1.5, trace, require_chip=False,
                        peaks=tiny.PEAKS)


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(root, workload):
    line = go(root, workload)
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert line["attempted"] == 4 + 12 and line["failed"] == 0   # fill, window
    assert set(line["metrics"]) == {"ttft_p90_ms", "itl_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["compiles_in_window"] == 0
    gap = line["checks"]["max_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]


def test_a_traced_run_reports_per_layer_metrics(root):
    line = go(root, "tiny-dense.chat", trace=True)
    assert line["correct"] is True
    m = line["metrics"]
    # no device plane in a CPU trace: device metrics have nothing to read
    assert {"claim_ready_ms", "queue_wait_p95_ms"} <= set(m)
    assert not {"idle_share.serve", "decode_chunk_roofline", "mfu.serve",
                "step_ms", "host_ms_per_tick"} & set(m)
    assert m["queue_wait_p95_ms"]["value"] >= 0


def unchanged_state(cfg):
    def step(p, t, c, bt, pos, adv, zb, rs):
        logits, _ = lm.decode_chunk(cfg, p, t, c, bt, pos, adv,
                                    zero_blocks=zb, reset_slots=rs)
        return logits, c
    return jax.jit(step)


def half_batch(cfg):
    def step(p, t, c, bt, pos, adv, zb, rs):
        # the first half: the engine fills the lowest free slot first
        half = t.shape[0] // 2
        logits, nc = lm.decode_chunk(cfg, p, t, c, bt, pos,
                                     adv.at[:half].set(0),
                                     zero_blocks=zb, reset_slots=rs)
        return logits.at[:half].set(0), nc
    return jax.jit(step)


def altered_token(monkeypatch):
    sample = engine_mod.ServeEngine._sample

    def bad(self, logits, r):
        tok = sample(self, logits, r)
        return (tok + logits.shape[-1] // 2) % logits.shape[-1] \
            if len(r.generated) == 1 else tok
    monkeypatch.setattr(engine_mod.ServeEngine, "_sample", bad)


FAULTS = ["unchanged_state", "half_batch", "altered_token"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_under_the_timed_path_is_caught(root, workload, fault,
                                                monkeypatch):
    if fault == "altered_token":
        altered_token(monkeypatch)
    else:
        monkeypatch.setattr(engine_mod, "_jitted_step",
                            {"unchanged_state": unchanged_state,
                             "half_batch": half_batch}[fault])
    line = go(root, workload)
    gap = line["checks"]["max_logit_gap"]
    assert line["correct"] is False
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_control_run_is_not_correct(root, workload):
    line = run.run_cell(root, workload, SEED, 1.5, False, require_chip=False,
                        peaks=tiny.PEAKS, control=True)
    gap = line["checks"]["max_logit_gap"]
    assert line["correct"] is False
    assert gap["value"] > gap["limit"]


def cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_chip_no_result():
    p = cli(tiny.REPO, "--workload", "danube-chat", "--seed", "5",
            "--seconds", "10", "--trace", "0")
    assert p.returncode == run.NO_CHIP_EXIT
    assert p.stdout.strip() == ""


def test_the_benchmark_alone_does_not_run(tmp_path):
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.REPO, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = cli(str(tmp_path), "--workload", "danube-chat", "--seed", "5",
            "--seconds", "10", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
