"""The engine's ``serve/`` spans: their arithmetic on a hand-built trace,
and a tiny paged engine stepped under the profiler, telemetry off and on."""
import types

import numpy as np
import pytest

import benchfix  # noqa: F401  (puts the harness on the path)
from harness import spans
from harness import trace as T


def _ev(name, start_us, dur_us):
    return types.SimpleNamespace(name=name, start_ns=start_us * 1e3,
                                 duration_ns=dur_us * 1e3)


def _step(t, host_us, gc_us=0):
    """One decode step from ``t`` (us): budget, decode dispatch, a 100 us
    wait for the tokens, then ``host_us`` of emit (with a collection of
    ``gc_us`` inside)."""
    evs = [_ev("serve/phase/budget", t, 2),
           _ev("serve/phase/decode", t + 2, 8),
           _ev("serve/decode", t + 3, 6),
           _ev("serve/phase/transfer", t + 10, 100),
           _ev("serve/phase/emit", t + 110, host_us)]
    if gc_us:
        evs.append(_ev("serve/gc/gen2", t + 111, gc_us))
    return [_ev("serve/step", t, 110 + host_us)] + evs


def _profile():
    """Three steps, one with a 30 us collection, 5 us apart; the device
    runs 12-110 us into each step."""
    line = lambda name, evs: types.SimpleNamespace(name=name, events=evs)
    plane = lambda name, lines: types.SimpleNamespace(name=name, lines=lines)
    host = (_step(0, 10) + _step(125, 40, gc_us=30) + _step(280, 10)
            + [_ev("engine_step", 0, 400), _ev("other", 0, 400)])
    tpu = plane("/device:TPU:0", [line("XLA Ops", [
        _ev("%op.1 = f32[2]", t + 12, 98) for t in (0, 125, 280)])])
    return types.SimpleNamespace(planes=[
        tpu, plane("/host:CPU", [line("python", host)])])


def test_program_spans_are_kept_apart_from_the_harness_spans():
    program = spans.from_profile(_profile())
    assert {e.name for e in program} == {
        "serve/step", "serve/phase/budget", "serve/phase/decode",
        "serve/decode", "serve/phase/transfer", "serve/phase/emit",
        "serve/gc/gen2"}
    assert [e.start for e in program] == sorted(e.start for e in program)
    # the harness's own reduction is what it was
    assert [h.name for h in T.from_profile(_profile()).host] == \
        ["engine_step"]


def test_host_ms_per_step_and_share():
    program = spans.from_profile(_profile())
    # transfers end at 110, 235, 390 and start at 10, 135, 290
    assert spans.host_intervals(program) == [
        (pytest.approx(110e-6), pytest.approx(135e-6)),
        (pytest.approx(235e-6), pytest.approx(290e-6))]
    assert spans.host_ms_per_step(program) == pytest.approx(
        1e3 * np.median([25e-6, 55e-6]))
    assert spans.host_share(program, 400e-6) == pytest.approx(
        100 * 80 / 400)
    assert spans.host_ms_per_step([]) is None
    assert spans.host_share([], 1.0) is None
    one = [e for e in program if e.name == spans.TRANSFER][:1]
    assert spans.host_ms_per_step(one) is None


def test_idle_gaps_named_by_the_innermost_span():
    tr = T.from_profile(_profile())
    program = spans.from_profile(_profile())
    idle = T.gaps(tr.ops[0])
    # device idle 110-137 (emit, then between steps, then budget and
    # decode of step 2) and 235-292 (the collection inside emit)
    gaps = spans.idle_gaps_by_phase(idle, program)
    assert [g[0] for g in gaps] == ["serve/gc/gen2", "outside serve/step"]
    assert [g[1] for g in gaps] == [pytest.approx(57e-6),
                                    pytest.approx(27e-6)]
    assert spans.idle_gaps_by_phase(idle, []) == [
        ["outside serve/step", pytest.approx(57e-6)],
        ["outside serve/step", pytest.approx(27e-6)]]
    assert len(spans.idle_gaps_by_phase(idle, program, k=1)) == 1


def test_host_phases_total_and_self():
    ph = spans.host_phases(spans.from_profile(_profile()))
    assert list(ph)[0] == "serve/step"
    assert ph["serve/step"]["count"] == 3
    assert ph["serve/step"]["total_s"] == pytest.approx(390e-6)
    # the step's own time is what its phases leave: nothing here
    assert ph["serve/step"]["self_s"] == pytest.approx(0.0, abs=1e-12)
    assert ph["serve/phase/decode"]["total_s"] == pytest.approx(24e-6)
    assert ph["serve/phase/decode"]["self_s"] == pytest.approx(6e-6)
    assert ph["serve/phase/emit"]["total_s"] == pytest.approx(60e-6)
    assert ph["serve/phase/emit"]["self_s"] == pytest.approx(30e-6)
    assert ph["serve/gc/gen2"] == {"count": 1,
                                   "total_s": pytest.approx(30e-6),
                                   "self_s": pytest.approx(30e-6)}
    assert spans.host_phases([]) == {}


@pytest.fixture(scope="module")
def tiny_engine_parts():
    import jax
    from repro.configs import get_config
    from repro.models import init_lm
    cfg = get_config("phi3-mini-3.8b").reduced()
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("telemetry", [False, True])
def test_engine_steps_hold_their_phases_in_order(tiny_engine_parts,
                                                 tmp_path, telemetry):
    import jax
    from repro.serve import Engine, Request, ServeConfig
    from repro.serve.telemetry import STEP_PHASES, remove_gc_hook
    cfg, params = tiny_engine_parts
    eng = Engine(params, cfg, ServeConfig(
        max_len=64, decode_batch=2, max_new_tokens=4, prefill_len=16,
        paged=True, page_size=8, telemetry=telemetry))
    eng.warmup()
    rng = np.random.default_rng(3)
    for uid in range(3):
        eng.submit(Request(uid=uid, prompt=rng.integers(
            0, cfg.vocab, size=6 + uid).astype(np.int32)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.drain()
    finally:
        jax.profiler.stop_trace()
        remove_gc_hook()
    tr, program = spans.load(str(tmp_path))
    assert tr.ops == {}                      # no TPU here
    steps = [e for e in program if e.name == spans.STEP]
    assert len(steps) >= 4
    n_emit = 0
    for st in steps:
        inside = [e for e in program if e.name.startswith("serve/phase/")
                  and st.start <= e.start and e.end <= st.end]
        names = [e.name.rsplit("/", 1)[1] for e in inside]
        assert names[:3] == ["budget", "admission", "prefill"]
        assert [STEP_PHASES.index(n) for n in names] == \
            sorted(STEP_PHASES.index(n) for n in names)
        if "decode" in names:
            assert names[-3:] == ["decode", "transfer", "emit"]
            n_emit += 1
        dispatch = [e for e in program if e.name == "serve/decode"
                    and st.start <= e.start and e.end <= st.end]
        assert len(dispatch) == names.count("decode")
    assert n_emit >= 3
    assert spans.host_ms_per_step(program) > 0
    assert "serve/phase/emit" in spans.host_phases(program)
    assert "serve/prefill_chunk" in spans.host_phases(program)


def test_decode_counters_match_the_lane_table(tiny_engine_parts):
    """The engine's ``decode_slot_steps`` and ``decode_context_tokens``,
    which the traced run reads around its window, count what the lane
    table says at each decode dispatch: the lanes not mid-prefill, and
    their prompts plus tokens so far."""
    from repro.serve import Engine, Request, ServeConfig
    cfg, params = tiny_engine_parts
    eng = Engine(params, cfg, ServeConfig(
        max_len=64, decode_batch=2, max_new_tokens=6, prefill_len=16,
        paged=True, page_size=8))
    eng.warmup()
    seen, decode = [], eng._decode

    def noted(*a):
        st = eng.sched.table.active
        live = [s for s in st if s not in eng._prefill_jobs]
        seen.append((len(live), sum(st[s].prompt_len + len(st[s].tokens)
                                    for s in live)))
        return decode(*a)

    eng._decode = noted
    rng = np.random.default_rng(4)
    for uid, n in enumerate((6, 37, 20, 41)):     # some span three chunks
        eng.submit(Request(uid=uid, prompt=rng.integers(
            0, cfg.vocab, size=n).astype(np.int32)))
    s0 = eng.stats()
    eng.drain()
    s1 = eng.stats()
    got = {k: s1[k] - s0[k] for k in ("decode_steps", "decode_slot_steps",
                                      "decode_context_tokens")}
    assert got == {"decode_steps": len(seen),
                   "decode_slot_steps": sum(n for n, _ in seen),
                   "decode_context_tokens": sum(c for _, c in seen)}
    assert len({n for n, _ in seen}) > 1        # lanes filled and emptied
