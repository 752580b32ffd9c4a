"""A whole run of the harness on the CPU at the tiny sizes, its chip check
off, with the timed path broken underneath: ``correct`` must come out
false for each fault a one-chip sync cell can have, and true without one.

Run by path: ``python -m pytest bench/tests``."""
import time

import jax
import jax.numpy as jnp
import pytest

import harness
from tiny import tiny_cell

SEED = 2_147_483_651


def serve(seconds: float = 6.0) -> dict:
    return harness.run("tiny", SEED, seconds, False,
                       t_start_process=time.perf_counter(),
                       require_tpu=False, cell_override=tiny_cell(),
                       log=lambda s: None)


def test_rehearsal_leaves_nothing_to_compile_in_the_window():
    """The rehearsal serves the run's own steps first, so the window that
    replays them compiles no program."""
    cell = tiny_cell()
    cell["traffic"]["rehearse_steps"] = 40
    lines = []
    out = harness.run("tiny", SEED + 1, 2.0, False,
                      t_start_process=time.perf_counter(),
                      require_tpu=False, cell_override=cell,
                      log=lines.append)
    assert out["correct"], out["check"]
    steps = next(int(w.split(", ")[1].split()[0]) for w in lines
                 if w.startswith("window:") and "steps" in w)
    assert 0 < steps <= 40, lines
    assert "window: 0 compiles inside the window" in lines, lines


def _unchanged_step(model, optimizer, prox_mu):
    """A cohort step that returns every client's model untouched."""
    def run(global_b, xs, ys, masks, active):
        return global_b, jnp.zeros(active.shape[1], jnp.float32)
    return run


def _failed(out, name):
    return (not out["correct"]
            and out["check"][name]["value"] > out["check"][name]["limit"])


def test_sound_run_is_correct():
    out = serve()
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["value"] == 0.0 for c in out["check"].values()), out


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.experiments import runner
    monkeypatch.setattr(runner, "_multi_cohort_fn", _unchanged_step)
    assert _failed(serve(), "param_gap_median")


def test_half_the_cohort_left_out(monkeypatch):
    """The reduction drops every other client and averages the rest."""
    from repro.kernels import ops
    real = ops.fed_reduce

    def half(weights, rows, segments, num_segments, base=None, **kw):
        keep = (jnp.arange(weights.shape[0]) % 2) == 0
        return real(jnp.where(keep, weights, 0.0), rows, segments,
                    num_segments, base, **kw)

    monkeypatch.setattr(ops, "fed_reduce", half)
    assert _failed(serve(), "param_gap_median")


def test_accuracy_altered_where_it_is_produced(monkeypatch):
    from repro.experiments import runner
    real = runner.evaluate_stacked

    def shifted(items, **kw):
        return [a + 0.1 for a in real(items, **kw)]

    monkeypatch.setattr(runner, "evaluate_stacked", shifted)
    assert _failed(serve(), "acc_gap")


def test_controller_step_altered(monkeypatch):
    from repro.core.fedtune import FedTune
    real = FedTune.on_round

    def bumped(self, *a, **kw):
        hp = real(self, *a, **kw)
        return type(hp)(m=hp.m + 1, e=hp.e)

    monkeypatch.setattr(FedTune, "on_round", bumped)
    assert _failed(serve(), "hp_mismatch")


def test_no_chip_no_result(capsys):
    """The command refuses the CPU: non-zero exit, no result line."""
    import run
    assert jax.devices()[0].platform == "cpu"
    assert run.main(["--workload", "emnist.sync_grid", "--seed", "0",
                     "--seconds", "10", "--trace", "0"]) != 0
    assert '"metrics"' not in capsys.readouterr().out


@pytest.fixture(autouse=True)
def _cpu_only():
    if jax.devices()[0].platform != "cpu":
        pytest.skip("the self-tests run on the CPU")
