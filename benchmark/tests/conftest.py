"""``pytest benchmark/tests`` — by hand, on the CPU.

Four virtual devices, so that the training fixtures run data-parallel over
four replicas and the reference's per-replica arithmetic is exercised.  The
fixtures under ``fixtures/`` are toy widths: they are never cells.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")

_RENAME = {"resnet50-1chip": "tiny-resnet-one", "resnet50-dp4": "tiny-resnet-dp4",
           "gpt2m-train-1k": "tiny-gpt-train", "gpt2m-serve-chat": "tiny-gpt-serve"}


@pytest.fixture(scope="session")
def tiny_bench():
    """BENCHMARK.json with its cells swapped for the toy fixtures: the same
    metrics, the same harness, sizes a CPU can hold."""
    from benchmark import cells

    bench = cells.load_benchmark()
    bench["workloads"] = [
        {"name": "tiny-resnet-one", "config": "tiny-resnet",
         "traffic": "tiny-train-dp", "chips": 4, "why": "fixture"},
        {"name": "tiny-resnet-dp4", "config": "tiny-resnet",
         "traffic": "tiny-train-dp4", "chips": 4, "why": "fixture"},
        {"name": "tiny-gpt-train", "config": "tiny-gpt",
         "traffic": "tiny-train-lm", "chips": 4, "why": "fixture"},
        {"name": "tiny-gpt-serve", "config": "tiny-gpt",
         "traffic": "tiny-serve", "chips": 1, "why": "fixture"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({_RENAME[w] for w in m["workloads"]})
    return bench
