"""The whole run of a cell on the CPU, at the repo's reduced widths: set-up,
the window through the engine, the reference check and the result line."""
import io
import json
from contextlib import redirect_stdout

import pytest

from benchfix import cpu_device, tiny  # noqa: F401  (fixtures)


def run_cell(name, cpu_device, seed=7, seconds=2.0, trace=0):
    import run
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", name, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      device_check=cpu_device)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["tiny-mha.closed", "tiny-gqa.open"])
def test_cell_runs_and_is_correct(tiny, cpu_device, name):
    res = run_cell(name, cpu_device)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["mean_gap"]["value"] < 1e-3
    want = {"setup_s", "itl_p95_ms", "tok_s"} | (
        {"ttft_p90_ms"} if name.endswith(".open") else set())
    assert set(res["metrics"]) == want
    assert res["device"]["platform"] == "cpu"
