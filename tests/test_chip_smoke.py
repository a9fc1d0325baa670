"""``chip_smoke.py`` off the chip: it refuses to report without a TPU or
without the package beside it, and its serving checks — the response
contract and the cache-free reference comparison — pass on the reduced
pair and catch tokens that are off the reference argmax."""
import copy
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(out):
    return '"ok"' not in out.stdout


def test_refuses_without_tpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0 and _no_result(out), out.stdout
    assert "no TPU" in out.stderr


def test_refuses_without_package(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run(lone, tmp_path)
    assert out.returncode != 0 and _no_result(out), out.stdout


class _NoClock:
    def snapshot(self):
        return 0.0, 0, 0


@pytest.fixture(scope="module")
def served():
    import jax
    cs = _load()
    sys.modules.setdefault("chip_smoke", cs)
    from repro.configs.floe_pair import pair_configs
    dep = cs.build(*pair_configs(cs.PAIR))
    kind = jax.devices()[0].device_kind
    out = {k: cs.serve_and_check(dep, k, kind, _NoClock())
           for k in (0, cs.SPEC_K)}
    return cs, dep, out


def test_serving_checks_pass_on_reduced_pair(served):
    cs, _, out = served
    for ids in out.values():
        assert len(ids) == len(cs.PROMPTS)
        assert all(len(t) == cs.MAX_NEW for t in ids)
    # greedy reconciliation: speculation serves the same tokens
    assert out[0] == out[cs.SPEC_K]


@pytest.mark.parametrize("row", [0, 1])      # a cloud row, a private row
def test_reference_check_catches_wrong_tokens(served, row):
    cs, dep, _ = served
    res, _ = cs.serve(dep, 0)
    cs.check_against_reference(dep, res, "unchanged")
    bad = copy.deepcopy(res)
    v = dep.slm.cfg.vocab_size
    bad[row].stats.token_ids = [(t + v // 2) % v
                                for t in bad[row].stats.token_ids]
    with pytest.raises(cs.SmokeFailure, match="off the reference"):
        cs.check_against_reference(dep, bad, "corrupted")
