"""Set-up compile numbers from JAX's own compile-path events (``CompileClock``):
``compile_s`` is the union of trace, lowering and backend spans before the
window; ``cache_misses`` the programs this process had to compile and write."""


def read(context, *, field: str):
    return float(context["setup_split"][field])
