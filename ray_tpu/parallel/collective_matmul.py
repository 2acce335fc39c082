"""Tensor-parallel matmuls that hide their collective behind themselves.

A megatron block sums the outputs of its two row-parallel projections
over `tp`. That sum is a reduce-scatter plus an all-gather; between the
two the residual stream can live sharded over the sequence, and each
half can travel while the matmul beside it works on other rows (Wang et
al., "Overlap communication with dependent computation via
decomposition", ASPLOS 2023). The two functions here are those halves,
written as a ring of `tp` steps over `jax.lax.ppermute`, which the TPU
runs asynchronously:

  allgather_matmul      x (B, S/tp, D) @ w (D, F/tp)  -> (B, S, F/tp)
  matmul_reducescatter  x (B, S, F/tp) @ w (F/tp, D)  -> (B, S/tp, D)

Only `tp` is manual inside the `jax.shard_map`; every other mesh axis
stays with the SPMD partitioner, so the parameters' all-gathers and the
gradients' reduce-scatters over `fsdp` are the ones the program had.
The backward passes follow by transposition (a ppermute's transpose is a
ppermute), so a gather's backward is a scatter overlapped the same way.
Cutting a matmul along its rows changes no row's contraction: values
are those of `x @ w` with a `psum`, up to the order of the `tp` terms.

Inside a ring the sequence is a tuple of `tp` chunks IN RING ORDER:
chunk j is the rows of device `idx - j`. A consumer that treats rows
alike (an elementwise op, the other function here) can take the chunks
as they are (`chunks=True`), and nothing is copied to put them in
order; `_natural` / `_ring` convert where the order matters.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "tp"
Chunks = Tuple[jax.Array, ...]


def _shift(x):
    """Hand x to the next device of the ring."""
    n = jax.lax.axis_size(AXIS)
    return jax.lax.ppermute(x, AXIS, [(j, (j + 1) % n) for j in range(n)])


def _matmul(x, w):
    return jax.lax.dot_general(x, w, (((2,), (0,)), ((), ())))


def _dot_bwd(res, g):
    # the kernel's gradient is formed as (K, N), rows of x against rows of
    # g: jax's own rule forms (N, K) and transposes it, and inside a
    # shard_map nothing tells the compiler which way the optimizer wants
    # it, so that it re-lays out the parameter and both moments to match
    # (21 float32 transposes a layer a step, 2 GiB of temporaries)
    x, w = res
    return (jax.lax.dot_general(g, w, (((2,), (1,)), ((), ()))),
            jax.lax.dot_general(x, g, (((0, 1), (0, 1)), ((), ()))))


_dot = jax.custom_vjp(_matmul)      # x (B, S, K) @ w (K, N)
_dot.defvjp(lambda x, w: (_matmul(x, w), (x, w)), _dot_bwd)


def _alone(x):
    """x as a value of its own: the compiler may not fuse its producer
    with its consumer. A matmul fused with the add of what the ring
    delivers would wait for the delivery instead of running beside it."""
    return jax.lax.optimization_barrier(x)


def _to_natural(chunks: Chunks, idx) -> jax.Array:
    """Ring-ordered chunks -> the sequence in its own order (axis 1) on
    device `idx`. Position p holds chunk `idx - p`; the selects fuse into
    whatever reads the result."""
    n = len(chunks)
    if n == 1:
        return chunks[0]
    return jnp.concatenate(
        [jax.lax.select_n((idx - p) % n, *chunks) for p in range(n)],
        axis=1)


def _to_ring(x: jax.Array, idx) -> Chunks:
    """The sequence in its own order -> `tp` ring-ordered chunks on
    device `idx`; the slices fuse into the matmuls that read them."""
    n = jax.lax.axis_size(AXIS)
    s = x.shape[1] // n
    return tuple(jax.lax.dynamic_slice_in_dim(x, ((idx - j) % n) * s, s,
                                              axis=1) for j in range(n))


# each is the other's transpose; spelled out because differentiating the
# select / the slice would pad every chunk to the whole sequence
_natural = jax.custom_vjp(_to_natural)
_ring = jax.custom_vjp(_to_ring)
_natural.defvjp(lambda chunks, idx: (_to_natural(chunks, idx), idx),
                lambda idx, g: (_ring(g, idx), None))
_ring.defvjp(lambda x, idx: (_to_ring(x, idx), idx),
             lambda idx, g: (_natural(tuple(g), idx), None))


def _ring_index(mesh: Mesh) -> jax.Array:
    """Every device's place in the ring, handed to the shard_map as data
    (spec `P(AXIS)`, one element a device): `jax.lax.axis_index` lowers
    to a PartitionId, which the SPMD partitioner refuses where other
    mesh axes are left to it."""
    return jnp.arange(mesh.shape[AXIS], dtype=jnp.int32)


# Both are jitted so that a model's layers share one trace of each: the
# rings are traced, differentiated and transposed once a shape, not once
# a layer (16 layers of the training cell: 20.6 s of tracing on the chip
# machine's host without it; the compiled program is the same)
@functools.partial(jax.jit, static_argnames=("mesh", "chunks"))
def allgather_matmul(x: jax.Array, ws: Sequence[jax.Array], mesh: Mesh,
                     *, chunks: bool = False):
    """x: (B, S, D), the sequence sharded over `tp`; ws: column-parallel
    kernels (D, F_i), F_i sharded over `tp`. Returns x @ w_i for every
    kernel as (B, S, F_i) with the whole sequence and F_i over `tp`: the
    all-gather of x over the sequence, each chunk multiplied while the
    next one travels. The kernels share the one gather. `chunks`: every
    product as its `tp` ring-ordered chunks (module docstring), for
    `matmul_reducescatter` by way of elementwise ops."""
    def ring(idx, chunk, *ws):
        n = jax.lax.axis_size(AXIS)
        outs = [[] for _ in ws]
        for i in range(n):
            # on its way before it is multiplied: it travels meanwhile
            nxt = _shift(chunk) if i < n - 1 else None
            for out, w in zip(outs, ws):
                out.append(_dot(_alone(chunk), w))
            chunk = nxt
        return tuple(tuple(o) if chunks else _natural(tuple(o), idx[0])
                     for o in outs)

    n = mesh.shape[AXIS]
    out = P(None, None, AXIS)
    return jax.shard_map(
        ring, mesh=mesh, axis_names={AXIS},
        in_specs=(P(AXIS), P(None, AXIS, None)) + (P(None, AXIS),) * len(ws),
        out_specs=((out,) * n if chunks else out,) * len(ws),
        check_vma=False)(_ring_index(mesh), x, *ws)


@functools.partial(jax.jit, static_argnames=("mesh",))
def matmul_reducescatter(x: Union[jax.Array, Chunks], w: jax.Array,
                         mesh: Mesh) -> jax.Array:
    """x: (B, S, F), F sharded over `tp` (or its ring-ordered chunks from
    `allgather_matmul(chunks=True)`); w: a row-parallel kernel (F, D), F
    over `tp`. Returns the sum over `tp` of x @ w as (B, S, D) with the
    sequence sharded over `tp`: the reduce-scatter of the partial
    products, each chunk's partial computed while the running sum of the
    chunk before it travels."""
    chunked = isinstance(x, tuple)

    def ring(idx, x, w):
        xs = x if chunked else _ring(x, idx[0])
        n = len(xs)
        acc = None
        for i in range(n):
            # the sum that passes here at step i is of the rows of device
            # idx - i - 1, and is with its owner at step n - 1
            rows = xs[(i + 1) % n]
            part = _alone(_dot(rows, w))
            acc = part if acc is None else _shift(acc) + part
        return acc

    spec = P(None, None, AXIS)
    return jax.shard_map(
        ring, mesh=mesh, axis_names={AXIS},
        in_specs=(P(AXIS), (spec,) * len(x) if chunked else spec,
                  P(AXIS, None)),
        out_specs=P(None, AXIS, None),
        check_vma=False)(_ring_index(mesh), x, w)
