"""Fixtures of the benchmark's tests: the real manifest, and the manifest
with the toy cell added the way a later PR adds one -- new files under a
directory the manifest lists, new entries, no edit to the harness."""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def manifest():
    from benchmarks import harness
    return harness.load_manifest()


@pytest.fixture(scope="session")
def toy_manifest(manifest):
    m = copy.deepcopy(manifest)
    m["paths"].append("tests/benchmarks/toy")
    m["configs"].append({
        "name": "toy",
        "source": "tests/benchmarks/bench_toy_model.py (a rehearsal model, "
                  "not a published one)",
        "file": "tests/benchmarks/toy/configs/toy.json", "reduced": [],
        "why": "CPU rehearsal of the harness"})
    m["workloads"] += [
        {"name": "toy-b8-bsp-1chip", "config": "toy",
         "traffic": "toy-b8-bsp", "chips": 1, "why": "rehearsal, one device"},
        {"name": "toy-b8-bsp-4chip", "config": "toy",
         "traffic": "toy-b8x4-bsp", "chips": 4, "why": "rehearsal, the mesh"}]
    m["per_layer"].append({
        "name": "toy_traced_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "worker loop",
        "moves": "train_throughput",
        "workloads": ["toy-b8-bsp-1chip", "toy-b8-bsp-4chip"]})
    return m


@pytest.fixture(scope="session")
def toy_lm_manifest(toy_manifest):
    """The toy manifest with a token model added: a configuration cut to a
    chip's share (``reduced`` not empty), its reference with a batch and a
    training objective of its own, its FLOPs, a traffic mix and a cell.
    Files under ``tests/benchmarks/toy`` and entries appended: nothing the
    manifest or the benchmark had is edited."""
    m = copy.deepcopy(toy_manifest)
    m["configs"].append({
        "name": "toy_lm",
        "source": "theanompi_tpu/models/transformer_lm.py MoETransformerLM "
                  "(the repo's own token model at a rehearsal size, not a "
                  "published one)",
        "file": "tests/benchmarks/toy/configs/toy_lm.json",
        "reduced": ["n_layer", "moe_experts", "vocab"],
        "why": "CPU rehearsal of a token model cut to a chip's share"})
    m["workloads"] += [
        {"name": "toy-lm-b4-bsp-1chip", "config": "toy_lm",
         "traffic": "toy-lm-b4-bsp", "chips": 1,
         "why": "rehearsal: int32 ids, a routed layer, a training objective"},
        {"name": "toy-lm-b4-bsp-4chip", "config": "toy_lm",
         "traffic": "toy-lm-b4x4-bsp", "chips": 4,
         "why": "rehearsal: the same on the mesh, each chip its own rows"}]
    return m
