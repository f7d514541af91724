"""The float32 reference against the engine's own logits at the repo's
reduced widths: chunked prefill (prefix-cache hit included) and paged
decode through the int4 KV cache, MHA with full RoPE and GQA with qkv
bias and half-dim RoPE."""
import numpy as np
import pytest

from benchfix import TINY_ARCHS, tiny_model

WEIGHTS = {"bits": 3, "block": 32, "rank": 16, "container": "int8"}


def serve_with_logprobs(arch, seed, prompts, new_tokens, prefill_len=32):
    import jax
    from harness.weights import make_params
    from repro.configs import get_config
    from repro.models import init_lm
    from repro.models.quantize import quantized_abstract
    from repro.serve import Engine, Request, SamplingParams, ServeConfig
    cfg = get_config(TINY_ARCHS[arch]).reduced()
    ab = quantized_abstract(
        jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)),
        rank=16)
    params = make_params(seed, ab)
    eng = Engine(params, cfg, ServeConfig(
        decode_batch=2, max_len=256, kv_dtype="int4", paged=True,
        page_size=16, prefill_len=prefill_len))
    out = []
    for i, p in enumerate(prompts):          # one at a time: hits are exact
        hit0 = eng.stats()["prefix_hit_tokens"]
        eng.submit(Request(uid=i, prompt=p, params=SamplingParams(
            temperature=0.0, max_new_tokens=new_tokens, logprobs=1)))
        lps = []
        eng.on_token = lambda uid, tok, info: lps.append(
            (tok, info["logprob"]))
        eng.drain()
        out.append({"prompt": p, "served": np.array([t for t, _ in lps]),
                    "logprob": np.array([v for _, v in lps]),
                    "matched": eng.stats()["prefix_hit_tokens"] - hit0,
                    "chunk": eng._chunk_len})
    return out


@pytest.mark.parametrize("prefill_len", [32, None])
@pytest.mark.parametrize("arch", sorted(TINY_ARCHS))
def test_reference_matches_engine_logits(arch, prefill_len):
    from reference.forward import Reference
    seed = 2**33 + 5                         # past 32 signed bits
    rng = np.random.default_rng(0)
    model = tiny_model(arch)
    shared = rng.integers(0, model["vocab"], 64).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, model["vocab"], n)
                               ]).astype(np.int32) for n in (37, 50, 21)]
    # chunks of 32, and the engine's default: one chunk as wide as max_len
    got = serve_with_logprobs(arch, seed, prompts, new_tokens=12,
                              prefill_len=prefill_len)
    assert [g["matched"] for g in got] == [0, 64, 64]
    ref = Reference(model, WEIGHTS)
    # one call: a request with no prefix hit, two sharing one prefix
    for g, lg in zip(got, ref.served_logits(seed, got, batch=4, groups=3)):
        lse = np.log(np.sum(np.exp(lg - lg.max(-1, keepdims=True)), -1)) \
            + lg.max(-1)
        ref_lp = lg[np.arange(len(g["served"])), g["served"]] - lse
        # same weights, same int4 KV rounding, same chunk reads: only
        # float32 summation order differs
        np.testing.assert_allclose(g["logprob"], ref_lp, atol=2e-4)
        assert np.array_equal(g["served"], lg.argmax(-1))


def test_reading_the_chunk_from_storage_is_visible():
    """The chunk semantics matter: reading a prompt's own chunk from the
    int4 cache instead of fresh moves the logits well past rounding."""
    from reference.forward import Reference
    model = tiny_model("tiny-mha")
    ref = Reference(model, WEIGHTS)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, model["vocab"], 130).astype(np.int32)
    req = {"prompt": tokens[:120], "served": tokens[120:], "matched": 0}
    fresh, stored = (ref.served_logits(3, [dict(req, chunk=c)])[0]
                     for c in (256, 1))
    assert np.max(np.abs(fresh - stored)) > 1e-3
