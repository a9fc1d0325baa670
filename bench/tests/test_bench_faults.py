"""``correct`` comes out false when the timed path is broken underneath:
the whole run (tiny configuration, CPU, no look for a chip) with one
fault planted in the program after it is built."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import run


def _alter_tokens(system):
    """A served token altered where it is produced: the first active
    row's first token of every macro-step reaches the host changed."""
    dep = system.dep
    fetch = dep.fetch_traces
    vocab = system.cfg["slm"]["vocab_size"]

    def altered(x):
        toks, arrived, lat, w, emit, lost = fetch(x)
        toks = np.array(toks)
        rows = np.nonzero(np.asarray(emit)[0])[0]
        if rows.size:
            i = rows[0]
            toks[0, i] = 3 + (toks[0, i] + 1) % (vocab - 3)
        return toks, arrived, lat, w, emit, lost
    dep.fetch_traces = altered


def _leak_private(system):
    """Privacy routing broken: the detector clears every prompt."""
    system.engine.detector.detect = lambda text: False


def _all_private(system):
    """Privacy routing broken the other way: the detector flags every
    prompt, so no request reaches the cloud LLM or the fusion."""
    system.engine.detector.detect = lambda text: True


def _drop_fusion(system):
    """The cloud lane fuses nothing: Eq. 15 with w forced to 1."""
    dep = system.dep

    def slm_only(mlp, sl, ll, arrived):
        p = jax.nn.softmax(sl.astype(jnp.float32), -1)
        return p, jnp.ones(sl.shape[:1], jnp.float32)
    dep.fuse_batched = slm_only


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """One scratch checkout for the file: its compile cache is shared."""
    return tiny.scratch_root(tmp_path_factory.mktemp("tinyroot"))


@pytest.mark.parametrize("fault,number", [
    (_alter_tokens, "served_gap_max"),
    (_leak_private, "private_fused"),
    (_all_private, "route_mismatch"),
    (_drop_fusion, "served_gap_max"),
], ids=["token-altered", "private-leaked", "all-private",
        "fusion-dropped"])
def test_fault_makes_the_run_incorrect(root, fault, number):
    seen = {}
    res = run.execute(tiny.Args(seed=21), root=root, data=root,
                      platform="cpu",
                      hooks={"after_build": fault,
                             "after_check": lambda c, s, n, w, lim:
                                 seen.update(n)})
    assert res["correct"] is False
    assert not seen[number]["ok"]


def test_sound_run_is_correct_and_the_control_is_not(root):
    """The sound program passes; the control (the float32 reference with
    every matmul operand rounded to float8, its first choices in place of
    the served tokens) goes through the same comparison and fails it."""
    import check
    got = {}
    res = run.execute(tiny.Args(seed=22), root=root, data=root,
                      platform="cpu",
                      hooks={"after_check": lambda c, s, n, w, lim:
                             got.update(cfg=c, sample=s, numbers=n,
                                        window=w, limits=lim)})
    assert res["correct"] is True
    assert list(res)[-1] == "compared"
    numbers = check.compare(got["cfg"], 22, got["sample"], got["window"],
                            got["limits"], control=True)
    assert check.is_correct(numbers) is False
    assert not numbers["served_gap_max"]["ok"]


def _rec(private, served_private, cloud, tokens=5, status="ok"):
    from types import SimpleNamespace as NS
    return NS(req=NS(private=private), served_private=served_private,
              cloud_tokens=cloud, tokens=tokens, status=status)


@pytest.mark.parametrize("rec,number", [
    (_rec(False, True, 0), "route_mismatch"),
    (_rec(True, False, 5), "route_mismatch"),
    (_rec(True, True, 2), "private_fused"),
    (_rec(False, False, 3), "cloud_replies_lost"),
    (_rec(False, None, 0, 0, "rejected"), "never_served"),
], ids=["cloud-served-on-edge", "private-served-on-cloud",
        "private-fused", "cloud-reply-lost", "rejected"])
def test_counts_by_hand(rec, number):
    """Each exact count catches its fault, judged by the traffic's label
    and not by the lane the program chose; sound rows count nothing."""
    import check
    limits = {"served_gap_max": 1.0, "route_mismatch": 0,
              "private_fused": 0, "cloud_replies_lost": 0,
              "never_served": 0}
    sound = [_rec(False, False, 5), _rec(True, True, 0)]
    numbers = check.compare({}, 1, [], sound + [rec], limits)
    bad = {k for k, n in numbers.items()
           if k != "served_gap_max" and not n["ok"]}
    assert number in bad
    clean = check.compare({}, 1, [], sound, limits)
    assert all(n["value"] == 0 for k, n in clean.items()
               if k != "served_gap_max")

