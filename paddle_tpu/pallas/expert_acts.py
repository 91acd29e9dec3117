"""What one routed expert computes between the float32 products of its
matrices in front and its down projection, written ONCE: the XLA forms
of ``models/moe.py`` call these on whole arrays and the grouped GEMM
(``pallas/grouped_gemm.py``) as its kernel's epilogue on a tile.  A file
of their own, so that a program on the dense path names no line of the
kernel's file."""

import jax
import jax.numpy as jnp


def swiglu(g, u):
    return jax.nn.silu(g) * u


def relu2(u):
    return jnp.square(jax.nn.relu(u))
