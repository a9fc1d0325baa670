"""The plain reference against the program, at a tiny size of the
benchmark's own configuration file, on the CPU.

The program prefills a prompt and then decodes through its cache; the
reference runs one cache-free float32 forward over prompt + tokens.  The
program keeps its weights and activations in bfloat16 (8 significant
bits), so its log-probabilities differ from the float32 reference by
rounding: at logits of standard deviation ~3 and two layers, a few
2**-8 relative errors, about 0.05 nats.  TOL = 0.15 nats is three times
that; a logit corrupted by 0.5 must fail it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (sets sys.path)
import check
import reference as R
import system as S
import weights as W

TOL = 0.15
SEED = 3
PROMPT = [1] + [3 + b for b in b"river stone table window "]
STEPS = 6


@pytest.fixture(scope="module")
def built():
    cfg = tiny.tiny_config()
    sysm = S.System(cfg, SEED)
    return cfg, sysm


def _program_logits(sysm, lm, params, steps):
    """Prefill, then greedy decode through the cache: logits at every
    position that predicts a new token."""
    toks = jnp.asarray([PROMPT], jnp.int32)
    logits, cache = lm.prefill(params, {"tokens": toks}, 64)
    out, seq = [np.asarray(logits[0, -1], np.float32)], list(PROMPT)
    for _ in range(steps - 1):
        nxt = int(np.argmax(out[-1]))
        seq.append(nxt)
        logits, cache = lm.decode_step(params, cache,
                                       jnp.asarray([[nxt]], jnp.int32))
        out.append(np.asarray(logits[0, -1], np.float32))
    return np.stack(out), seq


@pytest.mark.parametrize("side", ["slm", "llm"])
def test_prefill_then_decode_matches_reference(built, side):
    cfg, sysm = built
    lm = sysm.slm if side == "slm" else sysm.llm
    params = sysm.dep.slm_params if side == "slm" else sysm.dep.llm_params
    prog, seq = _program_logits(sysm, lm, params, STEPS)
    want = list(range(len(PROMPT) - 1, len(PROMPT) - 1 + STEPS))
    ref = R.logits_at(W.base_key(SEED), side, cfg[side], cfg["weights"],
                      [seq], [want])[0][:STEPS]
    lp_prog = np.asarray(jax.nn.log_softmax(prog, -1))
    lp_ref = np.asarray(R.slm_logprobs(ref))
    assert np.abs(lp_prog - lp_ref).max() < TOL
    bad = lp_prog.copy()
    bad[2, int(np.argsort(bad[2])[-2])] += 0.5
    assert np.abs(bad - lp_ref).max() > TOL


def test_fusion_matches_program(built):
    """Eq. 14-15 in the reference equal the program's own fusion on the
    same logits (both float32)."""
    from repro.core import fusion as FUS
    cfg, sysm = built
    rng = np.random.default_rng(0)
    v = cfg["slm"]["vocab_size"]
    zs = jnp.asarray(rng.normal(size=(3, v)) * 3, jnp.float32)
    zl = jnp.asarray(rng.normal(size=(3, v)) * 3, jnp.float32)
    p, _ = FUS.fused_distribution(sysm.dep.mlp, zs, zl)
    align = W.draw_align(W.base_key(SEED), v, cfg["weights"])
    ours = np.asarray(R.fused_logprobs(align, zs, zl))
    np.testing.assert_allclose(ours, np.log(np.asarray(p)), atol=1e-4)


def test_stacked_draw_equals_layer_draw(built):
    """The program's one-call weight draw and the reference's per-layer
    draw give the same values."""
    cfg, _ = built
    key = W.base_key(SEED)
    stacked = W.draw_stacked(key, "slm", cfg["slm"], cfg["weights"])
    one = W.draw_layer(key, "slm", cfg["slm"], cfg["weights"], 1)
    for name, x in one.items():
        assert np.array_equal(np.asarray(stacked["layers"][name][1]),
                              np.asarray(x)), name


def test_seed_beyond_32_bits_is_its_own():
    a = np.asarray(jax.random.key_data(W.base_key(5)))
    b = np.asarray(jax.random.key_data(W.base_key(5 + (1 << 32))))
    assert not np.array_equal(a, b)


def test_served_gap_is_zero_at_argmax():
    lp = np.log(np.asarray([[0.1, 0.7, 0.2], [0.5, 0.25, 0.25]]))
    g = check.served_gaps([lp], [[1, 2]])[0]
    assert g[0] == 0 and abs(g[1] - np.log(2)) < 1e-9


def test_eos_logit_is_zero(built):
    """The end-of-sequence column of both unembeddings is zero, so no
    request ends before its drawn length."""
    cfg, sysm = built
    eos = cfg["weights"]["eos_token_id"]
    for params in (sysm.dep.slm_params, sysm.dep.llm_params):
        col = np.asarray(params["embed"]["unembed"]["w"][:, eos],
                         np.float32)
        assert not col.any()
