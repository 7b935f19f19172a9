"""chip_smoke.py's phases at `tiny` on the CPU mesh (the rehearsals of
the `on-chip-measurement` guide, kept as tests), and the proof that its
`main()` has no CPU mode. The chip run itself is `python chip_smoke.py`
on the machine with the chip."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

SEED = 22


@pytest.fixture(scope="module")
def loaded():
    """(runner, tables): TPC-H tiny behind a LocalQueryRunner."""
    return chip_smoke.load_phase(chip_smoke.TINY)


@pytest.fixture(scope="module")
def served(loaded):
    """Rehearsal 1: (results, MXU kernel calls) of the serve phase: a real
    CoordinatorServer + Client over HTTP, every statement twice, the warm
    execution compiling nothing (asserted inside the phase). The test,
    not the script, steers G3 onto the MXU kernel the CPU backend would
    not select."""
    runner, tables = loaded
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TRINO_TPU_FORCE_MXU", "1")
        with chip_smoke.mxu_spy() as calls:
            results = chip_smoke.serve_phase(runner, tables, SEED)
    return results, calls


@pytest.mark.parametrize("name", ["q6", "g3", "q3", "q1", "q18", "point"])
def test_served_statement_equals_numpy_reference(loaded, served, name):
    _, tables = loaded
    results, _ = served
    # (no order of `tiny` sums to over Q18's validation QUANTITY, 300:
    # its answer there is no row, from the engine and the reference both)
    assert results[name] or name == "q18", name
    chip_smoke.compare_phase({name: results[name]}, tables, SEED)


def test_served_shapes(served):
    results, calls = served
    assert len(results["point"]) == chip_smoke.N_POINT_LOOKUPS
    assert len(results["q3"]) == 10 and len(results["g3"]) > 64
    # G3 and Q1 reached the kernel, at the key domains and word rows
    # tests/test_chip_compile.py compiles for the chip; the proof of
    # device compiles the first call, G3's
    assert calls
    assert (calls[0]["limbs"], calls[0]["capacity"]) == ((8,), 160)
    q1 = (8, 8, 1, 4, 4, 4, 8, 1, 4, 4, 4, 8, 8, 8, 8)
    assert {(c["limbs"], c["capacity"]) for c in calls} == {((8,), 160), (q1, 12)}


def test_compare_fails_on_a_wrong_answer(loaded, served):
    _, tables = loaded
    results, _ = served
    wrong = {"q6": [[results["q6"][0][0] + 0.0001]]}
    with pytest.raises(AssertionError, match="q6 differs"):
        chip_smoke.compare_phase(wrong, tables, SEED)


def test_main_refuses_a_cpu_backend(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out
    # no way around it: nothing in the script picks a platform or an
    # interpreted kernel
    with open(chip_smoke.__file__) as f:
        source = f.read()
    for escape in ("JAX_PLATFORMS", "jax_platforms", "--allow-cpu",
                   "interpret=True"):
        assert escape not in source, escape


def test_mesh_phase_on_four_virtual_devices(loaded, monkeypatch):
    """Rehearsal 2: the --chips 4 phase on four of the eight virtual CPU
    devices: mesh plane, no fallback, all_to_all, feeds on all four, rows
    equal to the one-device runner's (all asserted inside the phase)."""
    import jax

    real_devices = jax.devices
    monkeypatch.setattr(
        jax, "devices", lambda *a, **kw: real_devices(*a, **kw)[:4]
    )
    runner, tables = loaded
    local_rows = {
        name: [list(r) for r in runner.execute(sql).rows]
        for name, sql in chip_smoke.MESH_STATEMENTS
    }
    chip_smoke.compare_phase(local_rows, tables, SEED)
    chip_smoke.mesh_phase(tables, local_rows, 4, chunk_rows=4096)
