"""A chunk's attention over its choice alone on the chip:
``ray_tpu/ops/block_attention.py:selected_block_attention`` at the shapes of
``deepseek-v3.2-exp.serve-longdoc`` (128 heads of 128 + 64 channels, a latent
cache of 512 + 64 rows and 33,280 positions, 2,048 positions kept), a parent
tree's form beside this one's. Run it through the chip tool, from the root
of the repo:

    python3 scripts/time_selected_attention.py [--parent DIR] [--rehearse]
        [key=value ...] [T:start ...]

A case is ``T:start`` (default: T 1,024 and 2,048 at starts 0, 4,096, 10,240
and 24,576), attended over the narrowest of ``kv_cache.CHOICE_WIDTHS`` that
holds it, as ``kv_cache._attend_chosen`` does. ``--parent DIR`` names a
checkout (``git archive <commit> | tar -x -C DIR``) whose
``ray_tpu/ops/block_attention.py`` is timed beside this tree's on the same
inputs; the two results are held to each other, and each to a plain float32
softmax over the choice on two of the heads. ``key=value`` sets a module
constant of this tree's ``block_attention`` for the cases after it. One
jitted function a form (the hand-over included: the queries turned
heads-major, the choice cast), two warm calls, then six inside one
``jax.profiler.trace``: the ``XLA Modules`` line's median. ``us_a_step`` is
that over the grid steps every row would make (heads x visible blocks),
``peak`` the share of 197 TFLOP/s that those steps' products (805 MFLOP each
at T = 2,048) reach: the parent's count of work on both sides, so the form
that leaves tiles out may read what no kernel that computes them all can. One JSON line a case on stdout and appended to
``chiprun_out/time_selected_attention.jsonl``. ``--rehearse``: toy shapes
through the interpreter on the CPU, no time.
"""
import importlib.util
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import kv_cache
from ray_tpu.ops import block_attention
from scripts.time_index_select import timed

PEAK = 197e12
SIZES = dict(H=128, R=512, Dn=128, Dr=64, Dv=128, S=33280, kept=2048)
# 192 ** -0.5 x YaRN's mscale squared at the published factor of 40
SCALE = 192 ** -0.5 * (0.1 * np.log(40.0) + 1.0) ** 2


def _parent(tree):
    spec = importlib.util.spec_from_file_location(
        "parent_block_attention",
        os.path.join(tree, "ray_tpu/ops/block_attention.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _choice(rng, start, T, width, kept):
    """[T, width] bool: ``kept`` of each row's visible positions (all where
    it sees fewer), drawn evenly, the row's own among them."""
    pos = start + np.arange(T)[:, None]
    keys = rng.random((T, width), dtype=np.float32)
    keys[np.arange(T), np.minimum(pos[:, 0], width - 1)] = 2.0
    keys = np.where(np.arange(width)[None, :] <= pos, keys, -1.0)
    kth = np.partition(keys, width - kept, axis=1)[:, width - kept]
    return keys >= np.maximum(kth, 0.0)[:, None]


def _plain(q, up, cache, picked, Dn, R):
    """Float32 all the way, whatever heads ``q`` and ``up`` hold."""
    rows = cache[1, 0, 0, :, :picked.shape[1]].astype(jnp.float32)
    q, up = q.astype(jnp.float32), up.astype(jnp.float32)
    kn = jnp.einsum("rhd,rs->hds", up[..., :Dn], rows[:R])
    v = jnp.einsum("rhd,rs->hds", up[..., Dn:], rows[:R])
    sc = (jnp.einsum("thd,hds->hts", q[..., :Dn], kn)
          + jnp.einsum("thd,ds->hts", q[..., Dn:], rows[R:])) * SCALE
    probs = jax.nn.softmax(jnp.where(picked[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hts,hds->thd", probs, v)


def case(T, start, parent, *, H, R, Dn, Dr, Dv, S, kept, rehearse=False):
    rng = np.random.default_rng(start + T)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    draw = lambda *shape: jnp.asarray(                       # noqa: E731
        rng.standard_normal(shape, dtype=np.float32), dtype)
    q, cache = draw(T, H, Dn + Dr), draw(2, 1, 1, R + Dr, S)
    up = draw(R, H, Dn + Dv) * R ** -0.5
    width = next((w for w in kv_cache.CHOICE_WIDTHS
                  if start + T <= w < S), S)
    picked = jnp.asarray(_choice(rng, start, T, width, kept))
    where = (jnp.int32(1), jnp.full((1,), start, jnp.int32))

    def ours(q, up, cache, picked):
        return block_attention.selected_block_attention(
            q, up, cache, picked, *where, scale=SCALE, interpret=rehearse)

    def theirs(q, up, cache, picked):
        return parent.selected_block_attention(
            q, up, cache, jnp.pad(picked, ((0, 0), (0, S - width))), *where,
            scale=SCALE, width=width, interpret=rehearse)

    got, ms = timed(ours, (q, up, cache, picked), "selected_new_" + "_".join(
        map(str, (T, start, *_constants().values()))))
    want = np.asarray(_plain(q[:, :2], up[:, :2], cache, picked, Dn, R))
    gap = lambda a: float(np.abs(                            # noqa: E731
        np.asarray(a[:, :2], np.float32) - want).max() / np.abs(want).max())
    bs = block_attention.SELECTED_POSITIONS
    steps = H * (min(start + T - 1, S - 1) // bs + 1)
    flop = 2 * bs * (R * (Dn + Dv) + T * (Dn + Dr + Dv))
    row = {"case": f"{T}:{start}", "width": width, "steps": steps,
           "tiles": block_attention.selected_tiles(start, T, S),
           "ms": ms, "gap_to_float32": gap(got), **_constants()}
    if parent is not None:
        if (T, start) not in _PARENTS:
            _PARENTS[T, start] = timed(
                theirs, (q, up, cache, picked), f"selected_parent_{T}_{start}")
        old, row["parent_ms"] = _PARENTS[T, start]
        row["parent_gap_to_float32"] = gap(old)
        row["gap_to_parent"] = float(np.abs(
            np.asarray(got, np.float32) - np.asarray(old, np.float32)).max())
    for name in ("ms", "parent_ms"):
        if row.get(name):
            side = name[:-2]
            row[side + "us_a_step"] = 1e3 * row[name] / steps
            row[side + "peak"] = steps * flop / (row[name] / 1e3) / PEAK
    return row


_PARENTS = {}     # a case's parent result and time, once a process


def _constants():
    return {"SELECTED_POSITIONS": block_attention.SELECTED_POSITIONS}


def main(args):
    parent = None
    if "--parent" in args:
        at = args.index("--parent")
        parent = _parent(args[at + 1])
        del args[at:at + 2]
    if "--rehearse" in args:
        block_attention.SELECTED_POSITIONS = 32
        if parent is not None:
            parent.SELECTED_POSITIONS = 32
        kv_cache.CHOICE_WIDTHS = (64, 128)
        sizes = dict(H=2, R=24, Dn=16, Dr=8, Dv=16, S=256, kept=16)
        for T, start in ((16, 40), (128, 64)):
            print(json.dumps(case(T, start, parent, **sizes, rehearse=True)))
        return
    os.makedirs("chiprun_out", exist_ok=True)
    if not any(":" in a for a in args):
        args = args + [f"{T}:{start}" for T in (1024, 2048)
                       for start in (0, 4096, 10240, 24576)]
    for a in args:          # in order: a constant, then the cases after it
        if "=" in a:
            setattr(block_attention, a.split("=")[0], int(a.split("=")[1]))
            continue
        T, start = map(int, a.split(":"))
        row = case(T, start, parent, **SIZES)
        print(json.dumps(row), flush=True)
        with open("chiprun_out/time_selected_attention.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
