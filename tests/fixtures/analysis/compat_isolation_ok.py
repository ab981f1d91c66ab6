"""Near miss: same surface shapes, but every probe targets non-jax
objects and versioned APIs come through the compat shim."""
import jax
from repro.dist.compat import make_mesh, program_memory


def make_grid(cfg):
    # getattr on a config object, not a jax module
    if getattr(cfg, "use_mesh", False):
        return make_mesh((2, 2), ("x", "y"))
    return None


def scale(x):
    return jax.numpy.tanh(x)


try:
    import tomllib                           # non-jax import gate is fine
except ImportError:
    tomllib = None


MEMORY = program_memory
