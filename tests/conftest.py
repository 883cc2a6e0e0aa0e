"""Test configuration: run the engine on a virtual 8-device CPU mesh.

Must set platform flags before the first jax import anywhere in the
test process, mirroring how the driver validates multi-chip sharding
without real chips.
"""

import os

# Force the CPU mesh even where the environment would pick an
# accelerator: the test suite validates sharding semantics on 8 virtual
# devices, not chip perf. Set SHADOW_TPU_TEST_PLATFORM to override.
_platform = os.environ.get("SHADOW_TPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# A pytest plugin may have imported jax before this file ran, after
# which the environment variable is no longer read: pin the platform
# through the config API as well.
import jax  # noqa: E402

jax.config.update("jax_platforms", _platform)

# The persistent compilation cache stays off here unless
# JAX_COMPILATION_CACHE_DIR asks for one: core.jitcache.
# place_compile_cache places <repo>/.jax_cache on accelerators only, so
# every test compiles from scratch and no entry outlives the run.

import pytest  # noqa: E402


# Under pytest-xdist's --dist loadfile each worker runs whole files, and
# xdist hands out the files with the most tests first. The files whose
# tests compile large window programs hold few tests, so they would
# start last and the run would end on them, past the tier-1 limit.
# These files are handed out first instead, longest first (per-file
# walls of a six-worker run on the CPU: test_compaction.py about 950 s
# down to test_autotune.py about 65 s), then the rest as xdist orders
# them. A new file that compiles a TCP window program belongs here.
HEAVY_FILES = (
    "test_compaction.py", "test_until_complete.py", "test_tgen.py",
    "test_differential.py", "test_compaction_invariants.py",
    "test_tcp.py", "test_multiproc.py", "test_distributed.py",
    "test_parallel.py", "test_socks.py", "test_hosting.py",
    "test_netscope.py", "test_shim.py", "test_checkpoint.py",
    "test_tpu_compile.py", "test_shrink.py", "test_obs.py",
    "test_gossip.py", "test_harness.py",
    "test_loopback.py", "test_digest.py", "test_faults.py",
    "test_supervision.py", "test_serving.py", "test_ladder.py",
    "test_autotune.py",
)
# Ahead of them, two files whose checks read the process or the machine
# and so want a fresh worker beside workers that are still collecting
# and tracing in Python: test_shim_libc.py's sleep test (first in its
# file) bounds its own wall, compile included, at 60 s (about 47 s on
# an idle machine; 63 s beside the TCP compiles), and
# test_memscope.py's watermark test needs the worker's resident set to
# fall by 128 MiB when it frees 256 MiB, which it did not in a worker
# holding 5 GB from the heavy files run before it.
FIRST_FILES = ("test_shim_libc.py", "test_memscope.py")
ORDER = FIRST_FILES + HEAVY_FILES


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); "
        "compile-heavy or long-wall tests")
    if (getattr(config.option, "dist", "no") == "loadfile"
            and hasattr(config.option, "loadscopereorder")):
        # the controller's scheduler keeps the workers' collection
        # order, which pytest_collection_modifyitems sets
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    # an xdist worker (its dist option reads "no") reports this order
    # to the controller's scheduler; a run without xdist keeps its own
    if not hasattr(config, "workerinput"):
        return

    def file_of(item):
        return item.nodeid.split("::", 1)[0]

    count = {}
    for item in items:
        count[file_of(item)] = count.get(file_of(item), 0) + 1

    def rank(item):
        f = file_of(item)
        name = f.rsplit("/", 1)[-1]
        if name in ORDER:
            return (ORDER.index(name), 0)
        return (len(ORDER), -count[f])
    items.sort(key=rank)


@pytest.fixture(scope="session")
def simple_topology_xml():
    """A 2-PoI topology equivalent to resource/topology.simple.graphml:
    20ms intra-vertex self-loops, 50ms inter-vertex link, no loss."""
    return SIMPLE_TOPOLOGY


SIMPLE_TOPOLOGY = """<?xml version="1.0" encoding="utf-8"?>
<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="packetloss" attr.type="double" for="edge" id="d9" />
  <key attr.name="jitter" attr.type="double" for="edge" id="d8" />
  <key attr.name="latency" attr.type="double" for="edge" id="d7" />
  <key attr.name="type" attr.type="string" for="node" id="d5" />
  <key attr.name="bandwidthup" attr.type="int" for="node" id="d4" />
  <key attr.name="bandwidthdown" attr.type="int" for="node" id="d3" />
  <key attr.name="geocode" attr.type="string" for="node" id="d2" />
  <key attr.name="ip" attr.type="string" for="node" id="d1" />
  <key attr.name="packetloss" attr.type="double" for="node" id="d0" />
  <graph edgedefault="undirected">
    <node id="poi-1">
      <data key="d0">0.0</data><data key="d1">0.0.0.0</data>
      <data key="d2">US</data><data key="d3">2048</data>
      <data key="d4">1024</data><data key="d5">net</data>
    </node>
    <node id="poi-2">
      <data key="d0">0.0</data><data key="d1">0.0.0.0</data>
      <data key="d2">US</data><data key="d3">2048</data>
      <data key="d4">1024</data><data key="d5">net</data>
    </node>
    <edge source="poi-1" target="poi-1">
      <data key="d7">20.0</data><data key="d8">0.0</data><data key="d9">0.0</data>
    </edge>
    <edge source="poi-1" target="poi-2">
      <data key="d7">50.0</data><data key="d8">0.0</data><data key="d9">0.0</data>
    </edge>
    <edge source="poi-2" target="poi-2">
      <data key="d7">20.0</data><data key="d8">0.0</data><data key="d9">0.0</data>
    </edge>
  </graph>
</graphml>
"""
