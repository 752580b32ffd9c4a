"""The control, at a size the CPU holds: the plain reference one precision
step down (bfloat16 model, float32 accounting) in the program's place must
fail the cell's limits, and the reference must read nought against
itself."""
import jax.numpy as jnp

from compare import judge, trial_numbers, worst
from reference import run_trial
from tiny import tiny_cell


def _spec(seed: int) -> dict:
    cell = tiny_cell()
    t, c = cell["traffic"], cell["config"]
    return dict(dataset=c["dataset"], reduced=True,
                batch_size=c["train"]["batch_size"], lr=c["train"]["lr"],
                eval_points=c["train"]["eval_points"],
                target_accuracy=c["train"]["target_accuracy"], seed=seed,
                preference=[0.25, 0.25, 0.25, 0.25], mode="sync", rounds=4,
                **t["grid"])


def test_control_fails_and_reference_agrees_with_itself():
    cell = tiny_cell()
    for seed in (3, 2_147_483_660):
        spec = _spec(seed)
        ref = run_trial(spec, cell["config"])
        same = worst([trial_numbers(ref, run_trial(
            spec, cell["config"], forced_acc=ref["history_acc"],
            served=ref["models"]))])
        assert judge(same, cell["limits"])[0], same
        assert all(v == 0.0 for v in same.values()), same
        ctl = run_trial(spec, cell["config"], dtype=jnp.bfloat16)
        got = worst([trial_numbers(ctl, run_trial(
            spec, cell["config"], forced_acc=ctl["history_acc"],
            served=ctl["models"]))])
        assert not judge(got, cell["limits"])[0], got
        assert got["param_gap_median"] > \
            cell["limits"]["param_gap_median"], got
