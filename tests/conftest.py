"""Test fixture: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's tier-1 strategy (SnappyFunSuite boots a real
embedded engine in one JVM — no mocks; core/src/test/scala/io/snappydata/
SnappyFunSuite.scala:51-88): tests run the real engine in-process, with
multi-"chip" behavior exercised via XLA host devices instead of real TPUs.

The platform is pinned to CPU here, before any backend initializes, so
the suite never takes the chip whatever the environment says.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# the package points the persistent compile cache at <checkout>/.jax_cache;
# keep the suite — and the children it starts, which inherit this — from
# filling the tree (the chip tool copies it as it stands) unless a cache
# directory was placed from outside
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
assert jax.default_backend() == "cpu", jax.default_backend()

import pytest  # noqa: E402

# ---- runtime lockdep witness (SNAPPY_TPU_LOCKDEP=1) -------------------
# snappydata_tpu.utils.locks enables itself from the env var at import
# (before any engine lock exists, since this conftest imports before the
# test modules import the package). Here we add the END-OF-SESSION
# check: zero cycle violations, and the observed acquisition-order graph
# must be a subgraph of the declared manifest (tools/locklint/
# lock_order.toml) — an edge tests actually exercised that the manifest
# does not allow fails the run.

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

_LOCKDEP = os.environ.get("SNAPPY_TPU_LOCKDEP", "").strip() in (
    "1", "true", "on")


def pytest_sessionfinish(session, exitstatus):
    if not _LOCKDEP:
        return
    from snappydata_tpu.utils import locks
    from tools.locklint import load_manifest

    problems = list(locks.violations())
    try:
        man = load_manifest()
    except Exception as e:
        problems.append("lockdep: cannot load lock_order.toml: %s" % e)
        man = None
    if man is not None:
        problems.extend(locks.assert_subgraph(man.allows))
    if problems:
        sys.stderr.write(
            "\n=== lockdep witness failures (%d) ===\n" % len(problems))
        for p in problems:
            sys.stderr.write(p + "\n")
        raise RuntimeError(
            "lockdep witness: %d problem(s); see stderr above — extend "
            "LOCK_ORDER.md + lock_order.toml only with a reviewed "
            "invariant" % len(problems))


@pytest.fixture()
def session():
    from snappydata_tpu import SnappySession
    from snappydata_tpu.catalog import Catalog

    s = SnappySession(catalog=Catalog())
    yield s
    s.stop()


@pytest.fixture(params=["select_to_the_constant", "gather_alone"])
def decode_form(request, monkeypatch):
    """Both lowerings of `device_decode.dict_decode`: as shipped (a
    dictionary up to `DICT_SELECT_MAX_WIDTH` slots by selects, a wider
    one by the gather) and with the constant at 0, where every
    dictionary takes the gather.  Read at trace time: a test builds its
    session, and so its plans, after asking for the fixture."""
    from snappydata_tpu.storage import device_decode

    if request.param == "gather_alone":
        monkeypatch.setattr(device_decode, "DICT_SELECT_MAX_WIDTH", 0)
    return request.param
