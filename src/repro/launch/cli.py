"""Shared CLI plumbing for mesh-aware drivers (launch/serve.py,
benchmarks/serve_bench.py, chip_smoke.py).

``force_host_devices`` must run BEFORE jax initializes its backends
(device counts are fixed at backend init), so this module imports no jax
at module level — drivers import it first, mutate the environment, and
only then import jax.
"""
from __future__ import annotations

import os
import re

_FORCE_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")
# the checkout root (src/repro/launch/cli.py -> three levels up)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places it, and no other
    directory is chosen here.  Otherwise the cache lives at ``.jax_cache/``
    in the checkout (git-ignored) — a fixed path, because the path is part
    of what makes a cached entry hit."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_host_devices(n: int) -> None:
    """CPU validation: fake ``n`` host devices via XLA_FLAGS.  No-op when
    ``n`` is falsy or the environment already forces at least ``n``
    devices; a smaller forced count is raised to ``n`` (the user asked for
    it explicitly — leaving a stale smaller value would dead-end them on
    the very error message that suggests this flag)."""
    if not n:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    m = _FORCE_RE.search(flags)
    if m:
        if int(m.group(1)) >= n:
            return
        flags = _FORCE_RE.sub("", flags).strip()
    os.environ["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={n}".strip()


def serving_mesh(dp: int, tp: int):
    """``jax.Mesh`` over ('data', 'model') for a dp x tp serving run, or
    None when dp*tp == 1 (single-device jits).  Fails with the
    --force-host-devices hint when the backend is short of devices."""
    if dp * tp <= 1:
        return None
    import jax
    from repro.launch.mesh import make_mesh
    n = len(jax.devices())
    if n < dp * tp:
        raise SystemExit(
            f"dp={dp} x tp={tp} needs {dp * tp} devices, have {n}; "
            f"on CPU pass --force-host-devices {dp * tp}")
    return make_mesh((dp, tp), ("data", "model"))
