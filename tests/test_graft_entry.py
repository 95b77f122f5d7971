"""``dryrun_multichip`` must work even when a backend initialized before
it could force the virtual CPU device count (the caller touched jax first,
so the dry run sees one device): it re-execs into a clean interpreter.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_in_process():
    # conftest already forced 8 virtual CPU devices; the direct path runs.
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as g
        g.dryrun_multichip(8)
    finally:
        sys.path.remove(REPO)


def test_dryrun_multichip_reexecs_when_backend_pinned():
    """Initialize a 1-device backend first; dryrun_multichip(8) must detect
    the shortfall and re-exec into a clean child interpreter that forces the
    virtual device count itself."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("_TFMESOS_DRYRUN_CHILD", None)
    # Parent sees exactly 1 CPU device (no forced count), so the guard trips.
    env["XLA_FLAGS"] = ""
    # Keep the grandchild's timeout inside ours so a slow machine fails with
    # the dryrun's RuntimeError (and no orphaned grandchild), not a raw
    # TimeoutExpired from this test's subprocess.run.
    env["_TFMESOS_DRYRUN_TIMEOUT"] = "240"
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "assert len(jax.devices()) == 1, jax.devices()\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(4)\n"
        "print('REEXEC_OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "REEXEC_OK" in proc.stdout
