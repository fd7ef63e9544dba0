"""Each configuration brings its own modules: the reference (weight layout,
loss, FLOP per token) and the program's side (``arch_of``), found through
the paths its file names.  The harness uses the very modules named, and a
file that is missing or lacks a function fails before any array is made."""
import dataclasses
import json

import pytest

from bench_fixtures import REPO, configs, tiny_cell

SEED = 3_000_000_019


@pytest.mark.parametrize("entry", configs(), ids=lambda c: c["name"])
def test_named_modules_give_the_programs_layout_and_the_flop_count(entry):
    """At the widths the file states: the reference's layout is the
    program's parameter layout, and its FLOP count at the file's
    ``flop_per_token.seq_len`` is the file's ``flop_per_token.value``."""
    from bench import spec
    from repro.models.registry import build_model
    from repro.utils.trees import tree_paths

    path = REPO / entry["file"]
    cfg = json.loads(path.read_text())
    mods = spec.config_modules(cfg, path, REPO)
    shapes = tree_paths(build_model(mods["program"].arch_of(cfg)).param_shapes())
    assert {k: tuple(v.shape) for k, v in shapes.items()} == \
        {k: v[0] for k, v in mods["reference"].layout(cfg).items()}
    assert "flop_per_token" in cfg, f"{path} states no flop_per_token"
    stated = cfg["flop_per_token"]
    assert mods["reference"].train_flops_per_token(cfg, stated["seq_len"]) == \
        stated["value"]


PLANTED = '''"""The dense reference with a planted fault: every loss 5 % high."""
from bench.reference import dense

layout = dense.layout
train_flops_per_token = dense.train_flops_per_token


def loss(cfg, precision, params, tokens, labels):
    return 1.05 * dense.loss(cfg, precision, params, tokens, labels)
'''


def test_the_reference_the_file_names_is_the_one_compared(tmp_path):
    """One program run, judged against two cells that differ only in the
    reference module their configuration names: a faithful copy reads
    correct, a module with a planted fault (loss x 1.05) does not."""
    from bench import cell as bcell

    faithful = tiny_cell(tmp_path / "faithful")
    (tmp_path / "faulty/bench/reference").mkdir(parents=True)
    (tmp_path / "faulty/bench/reference/planted.py").write_text(PLANTED)
    faulty = tiny_cell(tmp_path / "faulty",
                       cfg_over={"reference": "bench/reference/planted.py"})
    assert faulty.reference.__file__ == str(tmp_path / "faulty/bench/reference/planted.py")

    s = bcell.build(faithful, SEED)
    _, _, prog = bcell.drive_check_steps(s, SEED)
    ok, checks = bcell.compare(s, SEED, prog)
    assert ok, checks
    ok, checks = bcell.compare(dataclasses.replace(s, cell=faulty), SEED, prog)
    assert not ok, checks
    assert checks["loss_gap"]["value"] == pytest.approx(0.05, rel=0.1)


INCOMPLETE = "def layout(cfg):\n    return {}\n"


@pytest.mark.parametrize("over,error,names", [
    ({"reference": "bench/reference/absent.py"}, FileNotFoundError, "absent.py"),
    ({"program": "bench/programs/absent.py"}, FileNotFoundError, "absent.py"),
    ({"reference": "bench/reference/incomplete.py"}, AttributeError,
     "incomplete.py has no loss, train_flops_per_token"),
    ({"program": "bench/reference/incomplete.py"}, AttributeError,
     "incomplete.py has no arch_of"),
    ({"program": None}, KeyError, "tiny.json names no 'program'"),
], ids=["no_reference_file", "no_program_file", "reference_lacks_functions",
        "program_lacks_arch_of", "no_program_key"])
def test_missing_or_incomplete_module_fails_before_any_array(tmp_path, over,
                                                             error, names):
    import jax

    from bench import spec

    good = tiny_cell(tmp_path)
    (tmp_path / "bench/reference/incomplete.py").write_text(INCOMPLETE)
    cfg = dict(good.cfg, **over)
    cfg = {k: v for k, v in cfg.items() if v is not None}
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    before = len(jax.live_arrays())
    with pytest.raises(error, match=names.replace(".", r"\.")):
        spec.load(good.name, root=tmp_path, parts=tmp_path / "bench")
    assert len(jax.live_arrays()) == before


#: ``rtrain.run`` on the tiny cell (seed ``SEED``), as the tree gave it
#: before the reference's functions moved into per-configuration modules
PARENT = {
    "f32": {"losses": [6.220427513122559, 6.2100958824157715, 6.1418023109436035],
            "grad": {"embed": 2.0554757002477753, "blocks/l0/mlp/wo": 0.21261014748334356,
                     "blocks/l0/attn/bk": 0.007676923288238612},
            "change": {"embed": 0.4417400360107422, "blocks/l0/attn/wq": 0.1879756599664688,
                       "blocks/l0/mlp/wi": 0.2736584544181824},
            "sample": 0.2340252697467804},
    "fp8": {"losses": [6.218985557556152, 6.209726333618164, 6.144541263580322],
            "grad": {"embed": 2.058808577907992, "blocks/l0/mlp/wo": 0.21463186045564547,
                     "blocks/l0/attn/bk": 0.008252127493195486},
            "change": {"embed": 0.4405336380004883, "blocks/l0/attn/wq": 0.18838463723659515,
                       "blocks/l0/mlp/wi": 0.2746247351169586},
            "sample": 0.23342373967170715},
}


@pytest.mark.parametrize("precision", sorted(PARENT))
def test_reference_readings_are_the_parents(tmp_path, precision):
    """The move changed no arithmetic: the reference and its control read
    what they read before it, to rounding of the last bits."""
    import numpy as np

    from bench import cell as bcell
    from bench.reference import train as rtrain

    cell = tiny_cell(tmp_path)
    r = rtrain.run(cell.reference, cell.cfg, cell.workload["optimizer"], SEED,
                   bcell.reference_batches(cell, SEED), precision=precision)
    want = PARENT[precision]
    assert r.losses == pytest.approx(want["losses"], rel=1e-6)
    assert {k: r.grad_norms[k] for k in want["grad"]} == \
        pytest.approx(want["grad"], rel=1e-6)
    assert {k: r.change_norms[k] for k in want["change"]} == \
        pytest.approx(want["change"], rel=1e-6)
    assert float(np.linalg.norm(r.grad_samples["blocks/l0/attn/wv"])) == \
        pytest.approx(want["sample"], rel=1e-6)
