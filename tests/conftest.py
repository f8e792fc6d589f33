"""Test harness config: run everything on a virtual 8-device CPU mesh so
multi-chip sharding is exercised without TPU hardware (the driver separately
dry-runs the multichip path; see __graft_entry__.py).

jaxcfg.force_cpu pins the platform before any backend initializes; a test
never takes an accelerator, wherever the suite runs."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# small EC buckets: protocol tests check ONE signature at a time, and on
# this 1-core CPU box the pad lanes of the production 64-bucket are pure
# waste (measured: the bucket dominates suite wall-clock).  Must be set
# before lightning_tpu.crypto.secp256k1 imports.
os.environ.setdefault("LIGHTNING_TPU_VERIFY_BUCKET", "8")
os.environ.setdefault("LIGHTNING_TPU_SIGN_BUCKET", "8")

# The suite reads AND writes the persistent compile cache
# (jaxcfg.setup_cache: $JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache).  A cold EC-program compile is ~2 minutes of
# XLA:CPU time; the xdist workers share the directory, so a program one
# worker compiled is a load for every worker that needs it later.  (A
# reader that meets a half-written entry warns and compiles, jax 0.9.0.)

# The virtual 8-device mesh exists to exercise sharding CORRECTNESS,
# not to route every little verify through shard_map: the suite pins
# the single-device fused path; tests/test_zz_mesh_parity.py flips
# this on explicitly and asserts bit-identical output.
os.environ.setdefault("LIGHTNING_TPU_MESH_VERIFY", "off")

from lightning_tpu.utils.jaxcfg import force_cpu, setup_cache

force_cpu(n_devices=8)

import jax
import pytest

assert jax.default_backend() == "cpu", "tests must run on the CPU mesh"
assert jax.device_count() >= 8, "expected virtual 8-device CPU mesh"

setup_cache()


# Files whose first use of an EC program costs minutes of cold XLA:CPU
# compile, longest first (one file per process, six at a time, PR 23).
# They are few tests each, and xdist's default hands out files by test
# count, largest first — which left exactly these for the end of the
# run, one worker grinding through a compile while five sat idle.
# Handing them out first lets the short files fill the gaps.
_LONGEST_FIRST = (
    # (seconds-long, but asserts on the process-wide flight ring: it
    # must be a worker's first file, as alphabetical order made it)
    "test_attribution.py",
    "test_pallas_verify.py", "test_obs.py", "test_glv.py",
    "test_secp256k1.py", "test_zz_mesh_parity.py", "test_seeker.py",
    "test_gossip_origination.py", "test_pallas_engines.py",
    "test_zz_replay_pipeline.py", "test_external_vectors.py",
    "test_zz_obs_smoke.py", "test_pallas_secp.py", "test_gossip.py",
    "test_zz_resilience.py", "test_ingest.py", "test_zz_overload.py",
    "test_fault_matrix.py", "test_mpp.py", "test_gossipd.py",
    "test_channeld.py", "test_field.py",
)


# XLA:CPU maps every compiled kernel's code, rodata and data separately:
# one EC program is 3,000-8,000 memory maps, a worker that has compiled
# a dozen of them nears the kernel's vm.max_map_count (65,530), and the
# next compile or cache load then dies with SIGABRT / SIGSEGV — the
# "crash in long-running processes" this suite has always had (PR 23
# found a dying worker at 60,501 maps).  Past this many maps a module's
# teardown drops jax's executable caches; programs needed again are
# reloaded from the persistent cache.
_MAPS_HIGH_WATER = 40_000


@pytest.fixture(scope="module", autouse=True)
def _stay_under_max_map_count():
    yield
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
    except OSError:          # no procfs: nothing to go by
        return
    if n_maps > _MAPS_HIGH_WATER:
        jax.clear_caches()


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; register the marker so full-scale
    # soak/bench tests (test_zz_overload.py's loadgen storm) don't warn
    config.addinivalue_line(
        "markers",
        "slow: full-scale soak/bench runs excluded from tier-1")
    # under xdist, keep the order pytest_collection_modifyitems sets
    # (the --dist loadfile scheduler would re-sort files by test count)
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    # stable: order within a file, and among the other files, is kept
    items.sort(key=lambda it: rank.get(it.path.name, len(rank)))
