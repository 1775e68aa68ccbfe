"""The correctness check of each cell, driven on the CPU at a small size:
a sound run is correct, each fault of ``bench/faults.py``, planted
underneath the timed path, makes ``correct`` come out false, and the
control reads above the program.

The chip's own check is skipped: ``run_cell`` is called directly.
"""
import time

import jax
import pytest

import cell as cells
import faults

ROOT = cells.os.path.dirname(cells.BENCH)
SEED = 2 ** 31 + 11  # past the 31 bits of a signed int, as benchmark seeds may be
CELLS = [w["name"] for w in cells.load_json(ROOT, "BENCHMARK.json")["workloads"]]


def small(workload):
    cell = cells.find_cell(ROOT, workload)
    cfg = cell.config
    cfg["data"].update(n_train=6000, n_test=1500)
    if "centers" in cfg:
        cfg["centers"] = 300
    else:
        cfg["sampler"].update(lam=1e-3, m_cap=400)
    return cell


def run(cell):
    jax.clear_caches()
    return cells.run_cell(cell, SEED, 0.05, False, t_start=time.perf_counter(),
                          counter=cells.CompileCounter(), log=lambda _m: None)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = run(small(workload))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_controls_read_above_the_program(workload):
    """The control of ``control.py``: the program with its own bfloat16
    path switched on. At this size it reads far under the cell's limit,
    which is set from chip readings at the cell's own size (PERF.md); what
    carries over is that it reads several times what the program does."""
    import repro.api as api
    from repro.core.backend import PallasBackend

    cell = small(workload)
    unit = cells.load_unit(api, cell, cells.make_data(cell.config, SEED), SEED)
    unit.setup()
    unit.run()
    prog = unit.program_outputs()
    want = unit.reference_outputs(prog)
    got = unit.compare(prog, want)
    unit.backend = PallasBackend(bf16=True)
    unit.run()
    bf16 = unit.compare(unit.program_outputs(), want)
    assert all(bf16[k] > 3.0 * got[k] for k in got), (got, bf16)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(fault, workload):
    with faults.planted(fault):
        res = run(small(workload))
    assert not res["correct"], res["checks"]
