"""Inputs made from `--seed`, shared by the builders and the references.

The archive's scores and labels are made on the device in one jitted
call, the scorer's weights the same way in the served type, and the
record stream's tokens batch by batch. The program under test gets only
these inputs; the plain references regenerate them from the same seed
with the same functions and never read what the program made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, however many bits it has."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def stream_key(seed: int, name: str):
    """An independent key per named stream of one seed."""
    tag = int.from_bytes(name.encode()[:4].ljust(4, b"\0"), "little")
    return jax.random.fold_in(seed_key(seed), tag & 0x7FFFFFFF)


# -- the archive ----------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def _beta_corpus(key, n: int, alpha: float):
    """A ~ Beta(alpha, 1), drawn as U ** (1 / alpha); O ~ Bernoulli(A)."""
    k_a, k_o = jax.random.split(key)
    a = jax.random.uniform(k_a, (n,), jnp.float32) ** jnp.float32(1 / alpha)
    o = jax.random.uniform(k_o, (n,), jnp.float32) < a
    return a, o


def make_corpus(cfg: dict, seed: int):
    """(scores float32, labels bool) on the host, made on the device."""
    if cfg["beta"] != 1.0:
        raise ValueError("the corpus generator draws Beta(alpha, 1) only")
    a, o = _beta_corpus(stream_key(seed, "corpus"), int(cfg["records"]),
                        float(cfg["alpha"]))
    return np.asarray(a), np.asarray(o)


# -- the scorer -------------------------------------------------------------

def canonical_shapes(m: dict) -> dict:
    """The scorer's weights by their published roles, layers stacked."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim", d // h)
    f, v = m["intermediate_size"], m["vocab_size"]
    return {"embed": (v, d), "wq": (L, d, h * hd), "wk": (L, d, kv * hd),
            "wv": (L, d, kv * hd), "wo": (L, h * hd, d),
            "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
            "ln1": (L, d), "ln2": (L, d), "ln_f": (d,)}


NORMS = ("ln1", "ln2", "ln_f")


def make_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Random weights in one jitted call on the device: every matrix
    N(0, initializer_range) rounded to `dtype`, every norm scale 1."""
    shapes = canonical_shapes(m)
    names = sorted(shapes)
    std = float(m["initializer_range"])

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            if name in NORMS:
                out[name] = jnp.ones(shapes[name], jnp.float32)
            else:
                out[name] = (jax.random.normal(k, shapes[name], jnp.float32)
                             * std).astype(dtype)
        return out
    return gen(stream_key(seed, "weights"))


def make_token_batch(seed: int, batch_index, batch: int, seq_len: int,
                     vocab: int, marker, marker_rate: float):
    """Batch `batch_index` of the record stream: uniform random tokens,
    with a planted marker n-gram at a random offset in about
    `marker_rate` of the records."""
    return _token_batch(stream_key(seed, "tokens"), batch_index, batch,
                        seq_len, vocab, tuple(marker), float(marker_rate))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _token_batch(key, batch_index, batch, seq_len, vocab, marker,
                 marker_rate):
    k_tok, k_pos, k_off = jax.random.split(
        jax.random.fold_in(key, batch_index), 3)
    toks = jax.random.randint(k_tok, (batch, seq_len), 0, vocab, jnp.int32)
    planted = jax.random.uniform(k_pos, (batch, 1)) < marker_rate
    off = jax.random.randint(k_off, (batch, 1), 0, seq_len - len(marker))
    col = jnp.arange(seq_len)[None, :] - off
    mark = jnp.asarray(marker, jnp.int32)[jnp.clip(col, 0, len(marker) - 1)]
    inside = planted & (col >= 0) & (col < len(marker))
    return jnp.where(inside, mark, toks)
