"""JAX kernels: tensor schema, filter masks, score kernels, assignment solves."""

# Compiled executables must survive the process: scheduling code is
# "ready at binary start" in the reference (compiled Go); ours is ready
# at second process start via the persistent jax compilation cache
# (utils/compilecache.py says where it lives and how JAX's own switches
# move or disable it).  Enabled here — the compute root every solver
# path imports — rather than in the package __init__, so api/client/CLI
# consumers never pay the jax import.
from ..utils import compilecache as _compilecache

_compilecache.enable()
