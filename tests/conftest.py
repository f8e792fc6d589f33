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

# The suite runs WITHOUT the persistent compile cache.  Re-established
# on jax 0.9.0 / XLA:CPU (PR 23): with six xdist workers sharing a
# read-write cache, a worker died with SIGSEGV inside
# compilation_cache.get_executable_and_time (deserializing a complete,
# untruncated entry another worker had written) — once in one run of
# ~50 cache hits; the same read succeeds in a fresh process.  A dead
# worker costs more than the compiles the cache saves, so no test
# process reads or writes it (children inherit the setting).
os.environ.setdefault("LIGHTNING_TPU_JAX_CACHE_MODE", "off")

# The virtual 8-device mesh exists to exercise sharding CORRECTNESS,
# not to route every little verify through shard_map: the suite pins
# the single-device fused path; tests/test_zz_mesh_parity.py flips
# this on explicitly and asserts bit-identical output.
os.environ.setdefault("LIGHTNING_TPU_MESH_VERIFY", "off")

from lightning_tpu.utils.jaxcfg import force_cpu, setup_cache

force_cpu(n_devices=8)

import jax

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
    "test_pallas_verify.py", "test_obs.py", "test_glv.py",
    "test_secp256k1.py", "test_zz_mesh_parity.py", "test_seeker.py",
    "test_gossip_origination.py", "test_pallas_engines.py",
    "test_zz_replay_pipeline.py", "test_external_vectors.py",
    "test_zz_obs_smoke.py", "test_pallas_secp.py", "test_gossip.py",
    "test_zz_resilience.py", "test_ingest.py", "test_zz_overload.py",
    "test_fault_matrix.py", "test_mpp.py", "test_gossipd.py",
    "test_channeld.py", "test_field.py",
)


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
