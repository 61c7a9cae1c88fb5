from .base import DETERMINISTIC_BUILTINS, LAYERS, Layer  # noqa: F401
from . import (attention, conv, conv3d, conv_extra, core,  # noqa: F401
               decoder, recurrent, special, wrappers)

# Stochastic built-ins: these consume the per-layer PRNG key in apply().
# Every other BUILT-IN layer class is recorded as deterministic so the
# engines skip its per-vertex key split (see Layer.stochastic). Membership
# is by exact class: user-registered layers AND user subclasses of the
# built-ins keep the conservative "gets a key" default. Wrapper layers that
# define their own `stochastic` (property delegating to the wrapped layer)
# are left out of the set so their property stays in charge.
_STOCHASTIC_KINDS = {
    "dropout", "alpha_dropout", "gaussian_dropout", "gaussian_noise",
    "spatial_dropout", "autoencoder", "vae",
}
_PKG = __name__.rsplit(".", 1)[0]
for _kind, _cls in LAYERS.items():
    if (_kind not in _STOCHASTIC_KINDS
            and _cls.__module__.startswith(_PKG)
            and "stochastic" not in vars(_cls)):
        DETERMINISTIC_BUILTINS.add(_cls)
del _kind, _cls, _PKG
