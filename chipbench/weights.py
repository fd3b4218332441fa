"""Seeded random weights, made on the device by the benchmark.

The weights are laid out as the program's ``init_params`` lays them out
(layers stacked on a leading axis, norms stored as ``weight - 1``), so the
program serves them as they are, and the reference reads the same arrays.
They come from ``--seed`` in one jitted call, in the served dtype, and
placed in the sharding the caller gives, never leaf by leaf or on the
host.  The program's ``init_params`` is used for its shapes only, to check
that the two layouts still agree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import spec

# standard deviations: matrices 1/sqrt(fan_in); the embedding and the output
# head 0.02; biases and the norm weights' offsets from 1 are small but not
# zero, so a path that drops them shows in the logits; a family may scale a
# leaf's std (``STD_SCALE``) and centre a matrix's columns (``CENTRED``)
EMBED_STD = 0.02
BIAS_STD = 0.1
NORM_STD = 0.1


def layout(c: dict) -> dict:
    """{leaf path: (shape, kind)} of the served weights for config file
    ``c``, from its family's table; kind is "matrix", "embed", "bias" or
    "norm"."""
    return spec.family_of(c).layout(c)


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def param_count(c: dict) -> int:
    return sum(int(np.prod(s)) for s, _ in layout(c).values())


def check_layout(c: dict, program_cfg) -> None:
    """Raise if the program's ``init_params`` no longer has this layout."""
    from repro.models import init_params

    shapes = jax.eval_shape(lambda k: init_params(program_cfg, k),
                            jax.random.PRNGKey(0))
    have = {p: tuple(x.shape) for p, x in flatten(shapes).items()}
    want = {p: s for p, (s, _) in layout(c).items()}
    if have != want:
        raise ValueError(f"the program's parameter layout changed: "
                         f"program {have}, benchmark {want}")


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 2**31 and above included."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make(c: dict, seed: int, dtype=jnp.bfloat16, shardings=None) -> dict:
    """The served weights for config file ``c``, in one jitted call.

    ``shardings``: optional tree (as ``nest``ed paths) of shardings to
    place each leaf in as it is made."""
    family = spec.family_of(c)
    table = family.layout(c)
    paths = sorted(table)

    def build(key):
        keys = jax.random.split(key, len(paths))
        flat = {}
        for k, path in zip(keys, paths):
            shape, kind = table[path]
            std = (1.0 / np.sqrt(shape[-2]) if kind == "matrix" else
                   {"embed": EMBED_STD, "bias": BIAS_STD,
                    "norm": NORM_STD}[kind])
            if path in family.STD_SCALE:
                std *= family.STD_SCALE[path]
            # drawn in the served dtype: no float32 copy of a whole leaf
            x = jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype)
            if path in family.CENTRED:
                mean = jnp.mean(x, axis=-2, keepdims=True, dtype=jnp.float32)
                x = x - mean.astype(dtype)
            flat[path] = x
        return nest(flat)

    fn = jax.jit(build, out_shardings=shardings)
    return fn(key_for(seed))
