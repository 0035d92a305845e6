"""The harness: cells resolve by name, the loops run, the readers read,
and there is no CPU fallback."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from chipbench import generate, harness
from chipbench.tests.conftest import PAIRS, ROOT, run_tiny

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    c = harness.load_cell(cell)
    assert c.config["name"] == {w["name"]: w for w in BENCH["workloads"]}[cell]["config"]
    assert (ROOT / "chipbench" / "loops" / f"{c.traffic['loop']}.py").is_file()
    assert c.chips in (1, 4)
    names = [m["name"] for m in c.end_to_end + c.per_layer]
    assert "setup_s" in names
    assert len([m for m in c.end_to_end if m["name"] != "setup_s"]) >= 1
    assert c.per_layer
    for name in names:
        assert callable(harness.load_reader(name))


def test_benchmark_file_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in METRICS:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        # each listed cell reports the end-to-end metric this one moves
        for w in m["workloads"]:
            assert w in CELLS and w in moved.get("workloads", CELLS)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("chipbench/")
    for w in BENCH["workloads"]:
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").is_file()


def test_no_harness_code_names_a_cell():
    code = "".join(p.read_text() for p in (ROOT / "chipbench").glob("**/*.py")
                   if "tests" not in p.parts)
    for cell in CELLS:
        assert cell not in code


def test_run_fails_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr


def test_seed_above_32_bits_is_a_seed():
    a = generate.normal(generate.stream(2**33 + 1, generate.RHS), (4,))
    b = generate.normal(generate.stream(1, generate.RHS), (4,))
    c = generate.normal(generate.stream(2**33 + 1, generate.RHS), (4,))
    assert not np.array_equal(a, b) and np.array_equal(a, c)


def test_generated_band_keeps_the_law():
    band = np.asarray(generate.bands(generate.stream(3, generate.BANDS), 2, 64, 3, 1.0))
    assert band.shape == (2, 64, 7) and band.dtype == np.float32
    off = np.abs(band.astype(np.float64)).sum(axis=2) - np.abs(band[..., 3])
    # |a_ii| = d * sum |a_ij| at d = 1, to float32 rounding of the sum
    np.testing.assert_allclose(np.abs(band[..., 3]), off, rtol=1e-6)
    assert np.all(band[:, 0, :3] == 0) and np.all(band[:, -1, 4:] == 0)
    inner = np.delete(band[:, 3:-3], 3, axis=2)
    assert np.abs(inner).max() <= 1.0


READERS = sorted(p.stem for p in (ROOT / "chipbench" / "metrics").glob("*.py"))
TRACE_READERS = {"factor_roofline", "device_idle_pct.dense"}
END_TO_END = {"fresh": ["time_to_solution_s"]}


@pytest.mark.parametrize("config,traffic", PAIRS)
def test_loop_runs_and_readers_read(config, traffic, interpret):
    rec = run_tiny(config, traffic)
    assert rec["correct"] is True and rec["failed"] == 0 and rec["attempted"] > 0
    assert rec["compared"]["max_residual"]["value"] < 1e-5
    # every reader reads this record or finds nothing in it; those of the
    # trace find nothing, as the run was not traced
    values = {name: harness.load_reader(name)(rec) for name in READERS}
    assert all(v is None or v >= 0 for v in values.values()), values
    assert all(values[name] is None for name in TRACE_READERS)
    assert values["setup_s"] > 0
    assert all(values[name] > 0 for name in END_TO_END[traffic])
    metrics = [{"name": n, "unit": "u"} for n in ["setup_s"] + END_TO_END[traffic]]
    line = harness.result_line(harness.Cell("c", 1, rec["config"], rec["traffic"],
                                            metrics, []), rec, trace=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert set(line["metrics"]) == {m["name"] for m in metrics}
    json.dumps(line)


def test_traced_readers_read_a_reduced_trace():
    from chipbench import tracing

    trace = tracing.reduce(tracing.read_events(
        str(ROOT / "chipbench" / "tests" / "data" / "trace_small.json")))
    cfg = json.loads((ROOT / "chipbench" / "configs" / "sap_dense_200k_c.json").read_text())
    rec = {"trace": trace, "config": cfg, "steps": [{}],
           "device": {"kind": "TPU v5 lite"}}
    idle = harness.load_reader("device_idle_pct.dense")(rec)
    assert idle == pytest.approx(100 * (1 - trace["busy_s"] / trace["window_s"]))
    roof = harness.load_reader("factor_roofline")(rec)
    assert 0 < roof < 100
    assert harness.load_reader("device_idle_pct.dense")({**rec, "steps": []}) is None
    with pytest.raises(KeyError):
        harness.load_reader("factor_roofline")({**rec, "device": {"kind": "cpu"}})
