"""Execution backends: where/how a round's client fan-out runs (DESIGN.md §7)."""
from repro.api.registries import BACKEND_REGISTRY, register_backend
from repro.core.engine.backends.base import (ExecutionBackend,
                                             LINEAR_AGGREGATORS)
from repro.core.engine.backends.local import (LocalBackend,
                                              make_parallel_round_core)
from repro.core.engine.backends.mesh import MeshBackend

BACKENDS = ("local", "mesh")   # builtins


def _local_factory(**kw):
    return LocalBackend()


def _mesh_factory(*, mesh=None, strategy: str = "parallel", groups: int = 1,
                  reduce: str = "flat", **kw):
    """Default mesh: all host devices on a (devices, 1) data x model mesh —
    the geometry ``launch/train.py --backend mesh`` always used. Pass a
    concrete ``mesh`` to control the topology."""
    import jax
    from repro.launch.mesh import make_mesh
    if mesh is None:
        mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    return MeshBackend(mesh, strategy=strategy, groups=groups, reduce=reduce)


# builtin registrations — factory signature: f(*, strategy, groups, **kw)
register_backend("local", _local_factory)
register_backend("mesh", _mesh_factory)


def get_backend(name, **kw) -> ExecutionBackend:
    """Resolve a backend through the plugin registry; an
    ``ExecutionBackend`` instance passes through."""
    if isinstance(name, ExecutionBackend):
        return name
    return BACKEND_REGISTRY.get(name)(**kw)


__all__ = ["ExecutionBackend", "LINEAR_AGGREGATORS", "LocalBackend",
           "MeshBackend", "make_parallel_round_core", "BACKENDS",
           "get_backend"]
