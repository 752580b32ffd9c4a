"""A configuration's model is found by its ``kind``: a new kind is a new
file under ``models/``, taken with no edit anywhere else; an unknown kind
names the file it looked for; the reference and a kind module import
nothing of the program; and a configuration without a ``spec`` entry
leaves the trial specs as they were.

Run by path: ``python -m pytest bench/tests``."""
import hashlib
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

import harness
import kinds
from tiny import tiny_cell
from traffic import GridStream

BENCH = Path(__file__).resolve().parents[1]
SEED = 2_147_483_653


def _run(cell: dict, seed: int = SEED) -> dict:
    return harness.run("tiny", seed, 2.0, False,
                       t_start_process=time.perf_counter(),
                       require_tpu=False, cell_override=cell,
                       log=lambda s: None)


def test_new_kind_is_a_new_file(tmp_path, monkeypatch):
    if jax.devices()[0].platform != "cpu":
        pytest.skip("the self-tests run on the CPU")
    shutil.copy(kinds.MODELS / "mlp.py", tmp_path / "toy.py")
    monkeypatch.setattr(kinds, "MODELS", tmp_path)
    cell = tiny_cell()
    cell["config"]["model"]["kind"] = "toy"
    out = _run(cell)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert tmp_path / "toy.py" in kinds._loaded


def test_unknown_kind_names_the_missing_file():
    cell = tiny_cell()
    cell["config"]["model"]["kind"] = "no_such_kind"
    with pytest.raises(harness.BenchError,
                       match=r"models/no_such_kind\.py is missing"):
        _run(cell)


def test_reference_and_kind_import_nothing_of_the_program():
    code = (
        "import sys\n"
        f"sys.path.insert(0, {str(BENCH)!r})\n"
        "import kinds, reference\n"
        "mlp = kinds.load('mlp')\n"
        "model = {'kind': 'mlp', 'in_dim': 4, 'hidden': [3], 'n_classes': 2}\n"
        "mlp.init_params(model, 0)\n"
        "assert mlp.forward_flops(model) == 2 * (4 * 3 + 3 * 2)\n"
        "bad = [m for m in sys.modules if m == 'repro'"
        " or m.startswith('repro.')]\n"
        "assert not bad, bad\n"
        "print('clean')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=BENCH)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "clean"


def _digest(traffic: dict, config: dict, seed: int) -> str:
    stream = GridStream(traffic, config, seed)
    grids = [stream.next_grid() for _ in range(4)]
    return hashlib.sha256(json.dumps(grids).encode()).hexdigest()


@pytest.mark.parametrize("cell, seed, digest", [
    ("tiny", 2_147_483_651,
     "f723c517985dc6a8af9ac725f4229c8f3f1f28b33e5394c0417aede018269db1"),
    ("tiny", 5,
     "623f4b07ad2b3461001f16cdecd2f1d39f8d885ab50b61765fe2316137edd23b"),
    ("emnist.sync_grid", 2_147_483_651,
     "d99bc7ac062ef310f9e57332d988bf3c19e3ca0e991e49a2cb2d983c24e46283"),
])
def test_specs_without_a_spec_entry_are_unchanged(cell, seed, digest):
    """The digests are of the specs as the generator made them before it
    merged ``model["spec"]``."""
    c = tiny_cell() if cell == "tiny" else harness.load_cell(cell)
    assert "spec" not in c["config"]["model"]
    assert _digest(c["traffic"], c["config"], seed) == digest


def test_spec_entry_is_merged_into_every_spec():
    cell = tiny_cell()
    plain = GridStream(cell["traffic"], cell["config"], 9).next_grid()
    cell["config"]["model"]["spec"] = {"model": "resnet10"}
    merged = GridStream(cell["traffic"], cell["config"], 9).next_grid()
    assert merged == [dict(d, model="resnet10") for d in plain]
