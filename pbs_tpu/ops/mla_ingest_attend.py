"""One pass over the key blocks a block of a prompt's queries can see.

The attention of ``models/mla.py``'s prompt ingestion, per head, for a
block of ``Q`` queries that starts at position ``first``, under the
choice its indexer made (``seen``: causal already, ``top_mask``'s set,
ties in)::

    s[h, q, t] = q[h, q] . k[h, t] / sqrt(qk)
    o[h, q]    = softmax over the seen t of s[h, q, t] @ v[h]

in the per-head form (a head's keys and values read off the latent rows
once a prompt: 2 x (qk + v) operations a pair and head where the
absorbed form of the decode pays 2 x (kv_rank + rope + kv_rank)). As
``jax.numpy`` (``models/mla.py::_attend_chunks``, which stays as the CPU
lowering, as the path of a mesh and as this kernel's oracle) XLA:TPU
forms float32 ``(heads, Q, MLA_KEYS)`` scores, 128 MiB at 64 heads of
256 queries and 2,048 keys, and sends them through HBM four times a
chunk: 0.77 ms where the products take 0.17 (PERF.md section 6, PR 48).
Here a head's keys and values come into VMEM a block of ``tk``
positions at a time, the block's scores, mask, exponentials and its
part of the values' product are formed there with a float32 running
maximum, sum and accumulator, probabilities rounded to the values'
dtype before their product as ``_attend_chunks`` rounds them, and the
scores never leave the chip.

**It is ``ops/live_attend.py``'s machine with another reading of
"lane" and "rows"**: a lane is a *head*, its rows are the block's ``Q``
queries (the head's ``q`` whole a lane), the streamed operands are the
head's ``k`` and ``v`` blocks, and ``last`` is the key block the query
block's last position lies in, the same for every head. So **key blocks
past the query block's own end are neither fetched nor multiplied**:
the kernel takes the whole prompt's ``k`` and ``v`` whatever span the
block lies in, and what it runs is the causal triangle rounded up to
``tk``, where the spans of the ``jax.numpy`` form run five eighths of
the square. Positions past a query *inside* its last block are read
and masked, as the ``jax.numpy`` form masks them.

The choice is the same for every head, so it is not streamed a head:
it comes whole, int8, block by block down the rows (``(keys / tk) * Q,
tk)``: 2 MiB at 8,192 keys), fetched once a call, and a step reads its
block's ``Q`` rows of it.

At GLM-5's cell (64 heads of 192 + 64 and 256, 256 queries a block) a
pass of the last block of an 8,192-row prompt over all its keys takes
1.13 ms where the ``jax.numpy`` form's four chunks take 3.10, a whole
prompt's layer 24.0 ms for 65.0 (7.3 for 15.2 at 4,096 rows): 2.1 us a
grid step (a head, 256 x 1,024 keys), the MXU at ~60% inside the pass.
Key blocks of 512 take 27.0 (twice the steps), of 2,048 24.4 (a coarser
diagonal, and the 4,096 rung's spans are 1,024); a query tile of 512
(two of the ingestion's blocks a pass: not written) 21.4 (PERF.md
section 6, PR 48).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from pbs_tpu.ops.live_attend import live_attend

__all__ = ["ingest_attend", "ingest_attend_tiles", "ingest_block"]

#: Keys a block, the widest that divides a span of the prompt: a
#: head's ``k`` and ``v`` blocks of 512 KiB each at head dims of 256,
#: double-buffered, and float32 scores of 1 MiB a step.
BLOCKS = (1024, 512)
_F32 = jnp.float32


def ingest_block(keys: int) -> int:
    """Keys a block where a block of queries is given ``keys`` keys (a
    span of the prompt, or the spans up to its own; 0: the kernel's
    tiling does not take that length)."""
    return next((tk for tk in BLOCKS if keys % tk == 0), 0)


def ingest_attend_tiles(queries: int, qk: int, v: int, span: int) -> bool:
    """Whether the kernel's tiling takes a block of ``queries`` queries
    of heads ``qk`` and ``v`` wide over spans of ``span`` keys on a
    chip: head dims whole rows of 128 lanes, queries whole int8 tiles
    of 32 rows (the choice's), whole blocks of keys."""
    return (qk % 128 == 0 and v % 128 == 0 and queries % 32 == 0
            and ingest_block(span) > 0)


def _block(b, j, q_ref, seen_ref, k_ref, v_ref, *, scale: float):
    """Key block j of head b: the block's queries against the block's
    keys, a pair live where the query's indexer chose the key."""
    del b
    Q = q_ref.shape[1]
    nt = (((1,), (1,)), ((), ()))
    scores = jax.lax.dot_general(
        q_ref[0], k_ref[0], nt, preferred_element_type=_F32) * scale
    chosen = seen_ref[pl.ds(pl.multiple_of(j * Q, Q), Q), :]
    return scores, chosen.astype(jnp.int32) != 0, v_ref[0]      # (Q, tk)


def ingest_attend(q, k, v, seen, first, *, scale: float,
                  block: int | None = None, interpret: bool = False):
    """What every head of a block of queries reads off the keys it
    sees: ``q`` (H, Q, qk) the block's queries heads-major, ``k`` (H,
    S, qk) and ``v`` (H, S, v) the prompt's keys and values heads-major
    (all of them, or more than ``seen`` has: what lies past is not
    read), ``seen`` (Q, K) bool what each query attends among the first
    K keys (nothing past its own position; something at or before it),
    ``first`` the position of the block's first query (an int32
    scalar; ``first + Q <= K``). Returns (H, Q, v) in ``v``'s dtype.
    ``models/mla.py::_attend_chunks`` is the same function in
    ``jax.numpy``. Compiled, the shapes have to satisfy
    :func:`ingest_attend_tiles`; ``interpret`` (the tests) takes any
    whole blocks, and ``block`` (the tests) another block than
    :func:`ingest_block`'s."""
    H, Q, _ = q.shape
    K = seen.shape[1]
    tk = block or ingest_block(K)
    if not tk or K % tk:
        raise ValueError(f"{K} keys are not whole blocks of "
                         f"{tk or BLOCKS}")
    blocks = K // tk
    # the key block the query block's last position lies in (the
    # pipeline's ``last``: a grid step names no block of a head past
    # it), the same for every head
    last = jnp.broadcast_to(
        jnp.clip((jnp.asarray(first, jnp.int32) + Q - 1) // tk, 0,
                 blocks - 1), (H,))
    # the choice block by block down the rows: block j is rows
    # [j Q, (j + 1) Q), a whole (Q, tk) tile to read
    chosen = jnp.swapaxes(seen.astype(jnp.int8).reshape(Q, blocks, tk),
                          0, 1).reshape(blocks * Q, tk)
    place = lambda head, block: (head, block, 0)  # noqa: E731
    return live_attend(
        functools.partial(_block, scale=scale), last, (), [q],
        [chosen],
        [(k, (1, tk, k.shape[2]), place), (v, (1, tk, v.shape[2]), place)],
        blocks=blocks, out=jax.ShapeDtypeStruct((H, Q, v.shape[2]), v.dtype),
        vmem_limit_bytes=48 << 20, name="mla_ingest_attend",
        interpret=interpret)
