"""Weights and adapters made by the benchmark from ``--seed``.

The benchmark makes the weights, on the device, in one jitted call, in the
type they are served in (the configuration's dtype), so that the plain
reference can use the very same values without taking anything the
program made. They are laid out in the benchmark's own canonical form
(``base``: embedding, final norm and per-layer stacked ``[L, ...]`` leaves;
``lora``: ``{target: {"a": [L, K, r], "b": [L, r, N]}}``);
:func:`to_program` re-nests the same arrays into the program's parameter
tree, and the driver checks that tree against the program's own.

Distributions: frozen linears Student-t with 4 degrees of freedom at
variance 1/K (heavy tails, as trained weights have: a per-column int8
scale then rounds most weights coarsely, so the int8 control departs
from the reference by far more than bf16 rounding), q/k/v biases
N(0, 0.1²), norm
scales 1 + N(0, 0.1²), the embedding N(0, 0.02²), LoRA A N(0, 1/r) and
LoRA B zero, as a fine-tuning run starts (non-zero for serving tenants).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.flops import Widths, linears

BIASED = ("q", "k", "v")
EMBED_BLOCKS = 8     # the embedding is made in this many row blocks


def root_key(seed: int, salt: int = 0):
    """A PRNG key from a seed of up to 64 bits (seeds may pass 2**32)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & (2**64 - 1)), salt)


def _leaf(key, shape, std, dtype, mean=0.0, layers=None, heavy=False):
    """``mean + std·z`` in ``dtype``, one layer at a time when ``layers`` is
    given (so the float32 temporaries stay one layer's size and only the
    stored type is stacked), z standard normal or,
    with ``heavy``, Student-t with 4 degrees of freedom scaled to unit
    variance: z·(E₁ + E₂)^(-1/2) with E₁, E₂ exponential (χ²₄ = 2·(E₁ + E₂),
    and t₄ = z·(χ²₄/4)^(-1/2) has variance 2)."""
    def one(k):
        kz, k1, k2 = jax.random.split(k, 3)
        z = jax.random.normal(kz, shape, jnp.float32)
        if heavy:
            chi = jax.random.exponential(k1, shape, jnp.float32) \
                + jax.random.exponential(k2, shape, jnp.float32)
            z = z * jax.lax.rsqrt(chi)
        return (mean + std * z).astype(dtype)
    if layers is None:
        return one(key)
    return jax.lax.map(lambda l: one(jax.random.fold_in(key, l)),
                       jnp.arange(layers))


def base_shapes(w: Widths) -> dict:
    out = {"embed": (w.vocab, w.d), "final_norm": (w.d,),
           "ln1": (w.layers, w.d), "ln2": (w.layers, w.d)}
    for name, k, n in linears(w):
        out[f"{name}_w"] = (w.layers, k, n)
        if name in BIASED:
            out[f"{name}_b"] = (w.layers, n)
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def make_base(w: Widths, key, dtype: str):
    """Frozen base weights for one seed, per-layer keys ``fold_in(leaf, l)``
    so that any one layer can be made again alone."""
    out = {}
    for i, (name, shape) in enumerate(sorted(base_shapes(w).items())):
        k = jax.random.fold_in(key, i)
        if name == "embed":
            blocks = EMBED_BLOCKS if shape[0] % EMBED_BLOCKS == 0 else 1
            v = _leaf(k, (shape[0] // blocks, shape[1]), 0.02, dtype, 0.0,
                      blocks).reshape(shape)
        elif name == "final_norm":
            v = _leaf(k, shape, 0.1, dtype, 1.0)
        elif name in ("ln1", "ln2"):
            v = _leaf(k, shape[1:], 0.1, dtype, 1.0, w.layers)
        elif name.endswith("_b"):
            v = _leaf(k, shape[1:], 0.1, dtype, 0.0, w.layers)
        else:
            v = _leaf(k, shape[1:], shape[1] ** -0.5, dtype, 0.0, w.layers,
                      heavy=True)
        out[name] = v
    return out


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def make_lora(w: Widths, key, dtype: str, b_std: float = 0.0):
    """LoRA factors of every target: A ~ N(0, 1/r); B ~ N(0, b_std²)
    (zero, as fine-tuning starts, when ``b_std`` is 0)."""
    out = {}
    for i, (name, k_in, n_out) in enumerate(sorted(
            (t for t in linears(w) if t[0] in w.targets))):
        ka, kb = jax.random.split(jax.random.fold_in(key, 1000 + i))
        a = _leaf(ka, (k_in, w.rank), w.rank ** -0.5, dtype, 0.0, w.layers)
        b = (_leaf(kb, (w.rank, n_out), b_std, dtype, 0.0, w.layers)
             if b_std else jnp.zeros((w.layers, w.rank, n_out), dtype))
        out[name] = {"a": a, "b": b}
    return out


def to_program(base: dict, lora: dict, w: Widths) -> dict:
    """The same arrays nested as the program's dense-decoder tree."""
    def lin(name, with_lora=True):
        p = {"w": base[f"{name}_w"]}
        if name in BIASED:
            p["bias"] = base[f"{name}_b"]
        if with_lora and name in lora:
            p["a"], p["b"] = lora[name]["a"], lora[name]["b"]
        return p

    return {
        "embed": {"tok": base["embed"]},
        "final_norm": base["final_norm"],
        "blocks": {
            "ln1": base["ln1"], "ln2": base["ln2"],
            "attn": {t: lin(t) for t in ("q", "k", "v", "o")},
            "mlp": {t: lin(t) for t in ("gate", "up", "down")},
        },
    }


def lora_tree(lora: dict) -> dict:
    """Only the LoRA leaves, at the program tree's paths (what an adapter
    store takes for one tenant)."""
    return {"blocks": {
        "attn": {t: dict(lora[t]) for t in ("q", "k", "v", "o") if t in lora},
        "mlp": {t: dict(lora[t]) for t in ("gate", "up", "down")
                if t in lora}}}


def lora_of(params: dict) -> dict:
    """The LoRA leaves of a program tree, in the canonical layout."""
    blocks = params["blocks"]
    out = {}
    for group in ("attn", "mlp"):
        for t, p in blocks[group].items():
            if "a" in p:
                out[t] = {"a": p["a"], "b": p["b"]}
    return out
