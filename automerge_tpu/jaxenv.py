"""Process-level JAX set-up shared by the entry points (chip_smoke.py,
benchmarks/run.py, tools/loadgen.py): which platform a run may use, which device
it got, and where compiled programs are cached. Everything here must be
called before the first device use — importing ``automerge_tpu`` does
not initialise the backend, the first kernel dispatch does.

A chip belongs to one process at a time, so an entry point that calls
``require_platform`` owns it from then on and must not start a child
that needs it.
"""

import os

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Fixed, inside the checkout: the directory is part of what makes a cold
# process find the previous process's entries, so it never carries a
# pid, a timestamp or a temp name.
COMPILE_CACHE_DIR = os.path.join(_ROOT, '.jax_cache')


def configure_compile_cache():
    """Turn on JAX's persistent compilation cache and return the
    directory in use. ``JAX_COMPILATION_CACHE_DIR`` places it from
    outside (JAX reads the variable itself; no path is set in code
    then); otherwise it lives at ``COMPILE_CACHE_DIR``. The thresholds
    drop to zero because most of this system's kernels compile in well
    under JAX's default 1 s floor and would never be cached."""
    import jax
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir', COMPILE_CACHE_DIR)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    return jax.config.jax_compilation_cache_dir


def device_stamp():
    """{'platform', 'device_kind', 'n_devices'} as JAX reports them.
    Initialises the backend."""
    import jax
    devices = jax.devices()
    return {'platform': devices[0].platform,
            'device_kind': devices[0].device_kind,
            'n_devices': len(devices)}


def require_platform(cpu=False):
    """Pin the platform, initialise the backend and return
    ``device_stamp()``. ``cpu=True`` is the caller's explicit request
    for a CPU run. Anything else must come up as ``tpu``: with
    ``JAX_PLATFORMS`` unset JAX would warn and carry on on the CPU when
    the chip fails to initialise, so the platform is asked for by name
    and a missing chip is an error here, not a slower run under the
    same metric names."""
    import jax
    if cpu:
        jax.config.update('jax_platforms', 'cpu')
    elif not os.environ.get('JAX_PLATFORMS'):
        jax.config.update('jax_platforms', 'tpu')
    stamp = device_stamp()
    if not cpu and stamp['platform'] != 'tpu':
        raise RuntimeError(
            f"JAX came up on platform {stamp['platform']!r} "
            f"({stamp['device_kind']}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); this run needs a TPU")
    return stamp
