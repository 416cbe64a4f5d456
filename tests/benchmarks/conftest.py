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
        "name": "toy", "source": "tests/benchmarks/bench_toy_model.py",
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
