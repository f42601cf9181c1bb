"""Mesh construction and SPMD execution of the core replication steps.

Re-exports are lazy (PEP 562): `parallel.lockstep` is jax-free, and an
eager mesh/engine import here would charge everything that imports it
the full jax initialization.
"""

__all__ = [
    "make_mesh",
    "pick_axes",
    "LocalEngineFns",
    "SpmdEngineFns",
    "make_local_fns",
    "make_spmd_fns",
]

_MESH = ("make_mesh", "pick_axes")


def __getattr__(name):
    if name in _MESH:
        from ripplemq_tpu.parallel import mesh

        return getattr(mesh, name)
    if name in __all__:
        from ripplemq_tpu.parallel import engine

        return getattr(engine, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
