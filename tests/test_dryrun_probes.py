"""Depth-probe extrapolation exactness (the dry-run cost methodology).

On a 1×1 mesh (single CPU device — no placeholder devices needed) the
extrapolated per-step costs from 2/4-layer unrolled probes must match a
direct fully-unrolled compile of a deeper config.
"""
import dataclasses

import pytest

from repro.configs import INPUT_SHAPES, get_config
from repro.configs.base import ShapeSpec


@pytest.fixture(scope="module")
def tiny_shape():
    return ShapeSpec("tiny_train", 64, 4, "train")


def _mesh11():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh()


def test_probe_extrapolation_matches_unrolled(tiny_shape, monkeypatch):
    from repro.launch import dryrun as DR
    monkeypatch.setitem(DR.INPUT_SHAPES, "tiny_train", tiny_shape)

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              num_layers=6)
    mesh = _mesh11()
    # ground truth: real depth, fully unrolled
    truth = DR.compile_combo(cfg, tiny_shape, mesh, unroll=True)
    # extrapolated from 2/4-layer probes
    est, meta = DR.extrapolate_costs(cfg, tiny_shape, mesh)
    rel = abs(est["flops"] - truth["flops"]) / truth["flops"]
    assert rel < 0.02, (est["flops"], truth["flops"])
    relb = abs(est["bytes"] - truth["bytes"]) / truth["bytes"]
    assert relb < 0.10, (est["bytes"], truth["bytes"])


def test_decode_probe_extrapolation(monkeypatch):
    from repro.launch import dryrun as DR
    shape = ShapeSpec("tiny_decode", 64, 4, "decode")
    monkeypatch.setitem(DR.INPUT_SHAPES, "tiny_decode", shape)
    cfg = dataclasses.replace(get_config("gemma3-1b").reduced(),
                              num_layers=6, global_every=2)
    mesh = _mesh11()
    truth = DR.compile_combo(cfg, shape, mesh, unroll=True)
    est, _ = DR.extrapolate_costs(cfg, shape, mesh)
    rel = abs(est["flops"] - truth["flops"]) / max(truth["flops"], 1.0)
    assert rel < 0.05, (est["flops"], truth["flops"])
