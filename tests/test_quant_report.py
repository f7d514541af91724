"""Quantize-time introspection (repro.obs.quant) + report tooling.

Runs the real SRR pipeline over a reduced model with a
:class:`QuantRecorder` threaded through, then checks the paper-facing
invariants of every record (energy split, rank budget, byte
accounting), validates the written report against
``tools/quant_report_schema.json`` with the repo's own validator, and
smoke-renders it through ``python -m tools.quant_report``.
"""
import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from repro.configs import get_config
from repro.core import api
from repro.core.api import PTQConfig
from repro.data import capture_calibration, data_config_for
from repro.models import init_lm, lm_loss
from repro.models.quantize import quantize_model_params
from repro.obs import NULL_QUANT_RECORDER, QuantRecorder
from repro.quant.base import QuantizerConfig

from tools.quant_report import main as render_main          # noqa: E402
from tools.validate_metrics import validate                 # noqa: E402

SCHEMA_PATH = os.path.join(REPO, "tools", "quant_report_schema.json")


@pytest.fixture(scope="module")
def quantized():
    """One SRR pass over the reduced model with a live recorder."""
    cfg = get_config("phi3-mini-3.8b").reduced()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rec = QuantRecorder()
    ptq = PTQConfig(method="srr", scaling="identity", rank=8,
                    quantizer=QuantizerConfig(kind="mxint", bits=3,
                                              block_size=32))
    qparams, reports = quantize_model_params(params, None, ptq,
                                             container="int8", recorder=rec)
    return cfg, qparams, rec, reports


def _schema():
    with open(SCHEMA_PATH) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# per-record invariants
# ---------------------------------------------------------------------------
def test_every_pass_recorded(quantized):
    _, _, rec, reports = quantized
    assert len(rec.records) == len(reports) > 0
    assert {r.name for r in reports} == set(rec.records)


def test_record_energy_and_rank_invariants(quantized):
    _, _, rec, _ = quantized
    for r in rec.records.values():
        assert 0.0 <= r.preserved_energy_fraction <= 1.0
        assert abs(r.preserved_energy_fraction
                   + r.quant_exposed_energy_fraction - 1.0) < 1e-9
        assert 0 <= r.k <= r.rank
        # MXINT 3-bit block-32: 3 + 8/32 effective bits
        assert r.bits == pytest.approx(3.25)
        # the spectrum head is descending (singular values of SW)
        head = r.singular_head
        assert head == sorted(head, reverse=True)
        assert r.scaled_err >= 0 and r.weight_err >= 0
        assert 0 < r.scaled_rel_err < 1.0


def test_record_matches_layer_report(quantized):
    _, _, rec, reports = quantized
    for rep in reports:
        r = rec.records[rep.name]
        assert r.scaled_err == pytest.approx(rep.scaled_err)
        assert r.weight_err == pytest.approx(rep.weight_err)
        assert r.k == rep.k_star and r.rank == rep.rank


def test_container_byte_accounting(quantized):
    _, _, rec, _ = quantized
    for r in rec.records.values():
        assert r.container == "int8"
        assert r.quant_bytes > 0 and r.lowrank_bytes > 0
        assert r.total_bytes == r.quant_bytes + r.lowrank_bytes


# ---------------------------------------------------------------------------
# report: schema pin + CLI render + Chrome trace
# ---------------------------------------------------------------------------
def test_report_validates_against_schema(quantized):
    _, _, rec, _ = quantized
    report = rec.build_report()
    schema = _schema()
    assert validate(report, schema, schema) == []
    s = report["summary"]
    assert s["layers"] == len(rec.records)
    assert s["total_bytes"] == s["quant_bytes"] + s["lowrank_bytes"]
    assert 0.0 <= s["mean_preserved_energy_fraction"] <= 1.0


def test_write_produces_report_and_trace(quantized, tmp_path):
    _, _, rec, _ = quantized
    path = str(tmp_path / "report.json")
    rec.write(path)
    with open(path) as f:
        report = json.load(f)
    schema = _schema()
    assert validate(report, schema, schema) == []
    trace = str(tmp_path / "report.trace.json")
    with open(trace) as f:
        events = json.load(f)["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X" and e.get("pid") == 3]
    assert len(spans) == len(rec.records)
    assert any(e.get("name") == "process_name" for e in events)


def test_cli_renders_tables_and_worst(quantized, tmp_path, capsys):
    _, _, rec, _ = quantized
    path = str(tmp_path / "report.json")
    rec.write(path)
    assert render_main([path, "--worst", "2"]) == 0
    out = capsys.readouterr().out
    assert "worst 2 layers" in out
    assert "pres%" in out and "s-rel-err" in out
    # every layer shows up in the table
    for name in rec.records:
        assert name in out


def test_cli_rejects_schema_violation(tmp_path, capsys):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 1, "config": {},
                               "summary": {}, "layers": {}}))
    assert render_main([str(bad)]) == 1
    assert "violates" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# null object
# ---------------------------------------------------------------------------
def test_null_recorder_is_inert_and_schema_clean():
    NULL_QUANT_RECORDER.record_layer("x", None, None, None, None, None, None)
    NULL_QUANT_RECORDER.attach_container("x", {}, "int8")
    report = NULL_QUANT_RECORDER.build_report()
    schema = _schema()
    assert validate(report, schema, schema) == []
    assert report["layers"] == {} and report["summary"]["layers"] == 0


def test_pipeline_without_recorder_unchanged(quantized):
    """recorder=None is the default and must not perturb the pass."""
    cfg = get_config("phi3-mini-3.8b").reduced()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    ptq = PTQConfig(method="srr", scaling="identity", rank=8,
                    quantizer=QuantizerConfig(kind="mxint", bits=3,
                                              block_size=32))
    _, reports = quantize_model_params(params, None, ptq)
    _, _, _, recorded_reports = quantized
    assert [(r.name, r.k_star, r.scaled_err) for r in reports] == \
        [(r.name, r.k_star, r.scaled_err) for r in recorded_reports]


# ---------------------------------------------------------------------------
# offline-pass matmul precision
# ---------------------------------------------------------------------------
class _PrecisionProbe:
    """Duck-typed recorder noting the matmul precision each pass ran at."""

    def __init__(self):
        self.seen = []

    def record_layer(self, *args):
        self.seen.append(jax.config.jax_default_matmul_precision)

    def attach_container(self, *args):
        pass


def _calibrated_srr_pass(recorder):
    """qera-exact SRR over the reduced model from fresh calibration."""
    cfg = get_config("phi3-mini-3.8b").reduced()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    ptq = PTQConfig(method="srr", scaling="qera-exact", rank=8,
                    quantizer=QuantizerConfig(kind="mxint", bits=3,
                                              block_size=32))
    stats = capture_calibration(
        params, cfg, data_config_for(cfg, seq_len=16, global_batch=2,
                                     seed=0),
        lambda c, p, b, cc: lm_loss(c, p, b, cc), n_batches=1)
    return quantize_model_params(params, stats, ptq, recorder=recorder)


def test_ptq_pins_highest_precision_and_cpu_containers_unchanged(
        monkeypatch):
    """The offline pass runs its f32 matmuls at full precision (the TPU
    default is bf16 passes). CPU f32 matmuls are full precision either
    way, so the containers match a pass at the default precision bit
    for bit."""
    import numpy as np
    probe = _PrecisionProbe()
    pinned, _ = _calibrated_srr_pass(probe)
    assert probe.seen and set(probe.seen) == {"highest"}
    monkeypatch.setattr(api, "PTQ_MATMUL_PRECISION", "default")
    probe = _PrecisionProbe()
    default, _ = _calibrated_srr_pass(probe)
    assert set(probe.seen) == {"default"}
    for a, b in zip(jax.tree_util.tree_leaves(pinned),
                    jax.tree_util.tree_leaves(default)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ptq_builds_one_scaling_per_weight_stack(monkeypatch):
    """The matrices of one stacked weight share their calibration stats,
    so S (an eigendecomposition) is built once per stack, not once per
    matrix."""
    built = []
    real = api.CalibStats.scaling
    monkeypatch.setattr(api.CalibStats, "scaling",
                        lambda self, kind: built.append(kind)
                        or real(self, kind))
    _, reports = _calibrated_srr_pass(None)
    stacks = {r.name.rsplit("[", 1)[0] for r in reports}
    assert built == ["qera-exact"] * len(stacks)
    assert len(stacks) < len(reports)
