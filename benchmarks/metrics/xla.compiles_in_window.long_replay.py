"""XLA compile: executables jit asked the backend for inside the measured
window (`jax.monitoring` backend_compile_duration events; a persistent-cache
retrieval counts too, since it stalls a request all the same). Should read
0: every width bucket is warmed in set-up."""


def read(ctx):
    return ctx['compiles']['compilations']
