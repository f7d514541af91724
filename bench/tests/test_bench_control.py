"""The comparison that decides ``correct`` has to fail what it should.

At the repo's reduced widths on the CPU, through the whole run (set-up,
window, reference), with the look for a chip skipped:

  * the control: the engine's own bfloat16 compute path in place of the
    float32 the configuration states (``seeds.py --control``);
  * a fault planted where tokens are produced: lane 0's sampled token
    is replaced by its neighbour;
  * a decode step that returns its state unchanged: the KV cache (and
    every lane's position) it was given, not the one it wrote.
"""
import io
import json
from contextlib import redirect_stdout

import pytest

from benchfix import cpu_device, tiny  # noqa: F401  (fixtures)


def lines(fn, argv, cpu_device):
    out = io.StringIO()
    with redirect_stdout(out):
        assert fn(argv, device_check=cpu_device) == 0
    return [json.loads(x) for x in out.getvalue().strip().splitlines()]


def test_control_in_bfloat16_is_not_correct(tiny, cpu_device):
    import seeds
    # bfloat16 moves a logit by hundredths: give the check enough served
    # tokens to see an argmax that the float32 reference does not share
    path = tiny / "traffic" / "tiny_closed.json"
    mix = json.loads(path.read_text())
    mix.update(check_requests=48,
               output_tokens={"dist": "uniform", "min": 16, "max": 24})
    path.write_text(json.dumps(mix))
    argv = ["--workload", "tiny-mha.closed", "--seeds", "21,22",
            "--seconds", "2"]
    sound = lines(seeds.main, argv, cpu_device)
    control = lines(seeds.main, argv + ["--control"], cpu_device)
    assert all(r["correct"] for r in sound)
    assert not any(r["correct"] for r in control)
    worst_sound = max(r["checks"]["mean_gap"]["value"] for r in sound)
    least_control = min(r["checks"]["mean_gap"]["value"] for r in control)
    assert least_control > 3 * max(worst_sound, 1e-6)


def _alter_lane0_token(engine_mod):
    """Lane 0's sampled token is replaced by its neighbour."""
    import jax.numpy as jnp
    real = engine_mod.sample_tokens

    def altered(logits, *lanes):
        tok = real(logits, *lanes)
        lane0 = jnp.arange(tok.shape[0]) == 0
        return jnp.where(lane0, (tok + 1) % logits.shape[-1], tok)
    return "sample_tokens", altered


def _keep_decode_state(engine_mod):
    """The decode step returns the cache it was given, not the one it
    wrote: no lane's K, V or position moves."""
    real = engine_mod.decode_step

    def stale(ctx, params, token, cache, cfg):
        logits, _ = real(ctx, params, token, cache, cfg)
        return logits, cache
    return "decode_step", stale


@pytest.mark.parametrize("fault", [_alter_lane0_token, _keep_decode_state])
@pytest.mark.parametrize("name", ["tiny-mha.closed", "tiny-gqa.open"])
def test_a_planted_fault_is_caught(tiny, cpu_device, monkeypatch, name,
                                   fault):
    import repro.serve.engine as engine_mod
    import run
    monkeypatch.setattr(engine_mod, *fault(engine_mod))
    res = lines(run.main, ["--workload", name, "--seed", "5", "--seconds",
                           "2", "--trace", "0"], cpu_device)[-1]
    assert res["correct"] is False
    assert res["checks"]["mean_gap"]["value"] > \
        res["checks"]["mean_gap"]["limit"]
