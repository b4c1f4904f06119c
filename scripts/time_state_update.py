"""The one-token state kernels (``ssm_update``, ``delta_update``,
``kda_update``: ``ops/ssm.py:visit_live``) alone on the chip, at the three
hybrid cells' shapes (PR 54). Run it through the chip tool, from the root of
the repo:

    python3 scripts/time_state_update.py [--tree DIR] [--tag NAME]
        [--pieces KB,..] [--depths N,..] [--ways as,dma,body] [--lives N,..]
        [--save FILE] [--rehearse] [kernel ...]
    python3 scripts/time_state_update.py compare A.npz B.npz

``--tree`` names the checkout whose ``ray_tpu`` is imported (default: this
one), so that a parent commit unpacked beside it is timed by the same code in
a process of its own. A kernel is timed three ways: as it is, with its body
emptied (``dma``: the walk's reads and writes alone; what it writes is what
the ring held) and with its DMAs emptied (``body``: the arithmetic alone, on
whatever VMEM holds). ``--pieces`` and ``--depths`` sweep ``ssm.PIECE_BYTES``
(in KB) and ``ssm.DEPTH`` where the tree has them.

All variants of one kernel and one live set run inside ONE
``jax.profiler.trace``, ten executions each after two warm ones; a variant's
time is the median device time of the custom call of the kernel's name
inside its own jitted program. One JSON line a variant on stdout and in
``chiprun_out/time_state_update.<tag>.jsonl``: ``us`` a call, ``us_a_visit``,
and ``roofline`` = the live slots' states read and written once at 819 GB/s
over that time (the state alone: the benchmark's shares also count the
convolution's rows).

``--rehearse`` is the CPU's: toy shapes through the interpreter, no capture
and no time.

``--save`` writes, for seeded inputs at two layers and up to 16 slots of each
shape and live sets of 0, 1, 2, 5 and all slots in scattered order, the
kernel's row and the stepped layer's state after the call; ``compare`` holds two such files to each other
bit for bit (the parent's kernel against the change's).
"""
import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import types

import numpy as np

# name -> (state [L, B, H, Dk|P, Dv|N], value columns, live sets timed)
SHAPES = {
    "ssm_update": ((36, 48, 64, 64, 128), 128, (12, 18)),
    "kda_update": ((5, 64, 32, 128, 128), 128, (8, 14)),
    "delta_update": ((12, 8, 30, 96, 256), 192, (1, 3)),
}
BYTES_PER_S = 819e9
REHEARSE = False


def inputs(name, shape, Dv, seed):
    """The step's small arrays for every slot of ``shape``."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    _, B, H, D, N = shape

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    if name == "ssm_update":
        return (f32(rng.normal(size=(B, H, D))),
                f32(np.abs(rng.normal(size=(B, H))) * 0.1),
                -f32(rng.uniform(1, 16, (H,))),
                f32(rng.normal(size=(B, N))), f32(rng.normal(size=(B, N))))

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    g_shape = (B, H, D) if name == "kda_update" else (B, H)
    return (f32(unit(rng.normal(size=(B, H, D))) * D ** -0.5),
            f32(unit(rng.normal(size=(B, H, D)))),
            f32(rng.normal(size=(B, H, Dv))),
            -f32(rng.uniform(0.001, 3.0, g_shape)),
            f32(rng.uniform(0, 1, (B, H))))


def live_of(slots, B):
    """``decode_attention.live_slots``' [B + 1]: the indices, their count
    last."""
    import jax.numpy as jnp
    live = np.zeros(B + 1, np.int32)
    live[:len(slots)] = slots
    live[B] = len(slots)
    return jnp.asarray(live)


class _NoDMA:
    """``pltpu`` with copies that neither start nor wait."""

    def __init__(self, real):
        self._real = real

    def __getattr__(self, key):
        return getattr(self._real, key)

    def make_async_copy(self, *_):
        return types.SimpleNamespace(start=lambda: None, wait=lambda: None)


def variants(name, args):
    """tag -> a function that sets the modules up for it and undoes it."""
    from ray_tpu.ops import delta_rule, ssm
    body_of = ssm if name == "ssm_update" else delta_rule
    out = {}

    def setting(**changes):
        def enter():
            old = {}
            for key, value in changes.items():
                mod, attr = {"body": (body_of, "_step"),
                             "pltpu": (ssm, "pltpu")}.get(key, (ssm, key))
                old[key] = (mod, attr, getattr(mod, attr))
                setattr(mod, attr, value)
            return lambda: [setattr(m, a, v) for m, a, v in old.values()]
        return enter

    sweeps = [{}]
    if hasattr(ssm, "PIECE_BYTES"):
        sweeps = [{"PIECE_BYTES": kb << 10, "DEPTH": d}
                  for kb in (args.pieces or [ssm.PIECE_BYTES >> 10])
                  for d in (args.depths or [ssm.DEPTH])]
    for sweep in sweeps:
        tag = "_".join(f"{k[0].lower()}{v >> 10 if k[0] == 'P' else v}"
                       for k, v in sweep.items()) or "whole"
        ways = {"as": {}, "dma": {"body": lambda *a: None},
                "body": {"pltpu": _NoDMA(ssm.pltpu)}}
        for way in args.ways:
            out[tag + "." + way] = setting(**ways[way], **sweep)
    return out


def kernel_fn(name):
    import functools

    from ray_tpu.ops import delta_rule, kda, ssm
    return functools.partial(
        {"ssm_update": ssm.ssm_update, "kda_update": kda.kda_update,
         "delta_update": delta_rule.delta_update}[name], interpret=REHEARSE)


def time_kernel(name, args, say):
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import trace as tr

    shape, Dv, lives = SHAPES[name]
    L, B = shape[:2]
    small = inputs(name, shape, Dv, 0)
    states = jnp.full(shape, 0.5, jnp.float32)
    rng = np.random.default_rng(1)
    for n in args.lives or lives:
        live = live_of(np.sort(rng.permutation(B)[:n]), B)
        fns = {}
        for tag, enter in variants(name, args).items():
            leave = enter()

            def fn(s, live, *small):
                return kernel_fn(name)(s, jnp.int32(L // 2), *small,
                                       live=live)

            fn.__name__ = "v_" + tag.replace(".", "_")
            f = jax.jit(fn, donate_argnums=0)
            try:    # traced and compiled under this variant's settings
                for _ in range(2):
                    y, states = f(states, live, *small)
                y.block_until_ready()
                fns[tag] = f
            except Exception as e:   # a ring that VMEM does not hold
                say(kernel=name, live=n, variant=tag,
                    error=str(e).splitlines()[0][:300])
            leave()
        if REHEARSE:
            say(kernel=name, live=n, ran=sorted(fns))
            continue
        where = tempfile.mkdtemp(prefix="time_state_update.")
        with jax.profiler.trace(where):
            for f in fns.values():
                for _ in range(10):
                    y, states = f(states, live, *small)
            y.block_until_ready()
        dev = tr.load(where).devices[0]
        shutil.rmtree(where, ignore_errors=True)
        calls = [(s, d) for n_, s, d in dev.ops if n_.startswith(name)]
        moved = 2 * n * 4 * int(np.prod(shape[2:]))
        for tag, f in fns.items():
            inside = [d for m, ms, md in dev.modules
                      if f.__name__ in m
                      for s, d in calls if ms <= s < ms + md]
            us = statistics.median(inside) / 1e3
            say(kernel=name, live=n, variant=tag, us=round(us, 2),
                us_a_visit=round(us / n, 3), runs=len(inside),
                roofline=round(100 * moved / BYTES_PER_S / (us / 1e6), 2))
    del states


def record(name):
    """name.live -> the kernel's row and the state after, seeded."""
    import jax.numpy as jnp
    shape, Dv, _ = SHAPES[name]
    B = min(shape[1], 16)
    shape = (2, B) + shape[2:]
    rng = np.random.default_rng(2)
    states = jnp.asarray(rng.normal(size=shape), jnp.float32)
    small = inputs(name, shape, Dv, 3)
    out = {}
    for n in (0, 1, 2, 5, B):
        slots = rng.permutation(B)[:n]      # scattered, not ascending
        y, after = kernel_fn(name)(states + 0, jnp.int32(1), *small,
                                   live=live_of(slots, B))
        out[f"{name}.{n}.y"] = np.asarray(y)
        assert bool((after[0] == states[0]).all())     # the other layer
        out[f"{name}.{n}.state"] = np.asarray(after[1])
    return out


def compare(a, b):
    a, b = np.load(a), np.load(b)
    both = sorted(set(a.files) & set(b.files))
    differ = [k for k in both if not np.array_equal(
        a[k].view(np.uint32), b[k].view(np.uint32))]
    print(json.dumps({"compared": len(both), "bit_identical": not differ,
                      "only_in_one": sorted(set(a.files) ^ set(b.files)),
                      "differ": differ}))
    return 0 if both and not differ else 1


def main():
    if sys.argv[1:2] == ["compare"]:
        return compare(*sys.argv[2:4])
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.getcwd())
    ap.add_argument("--tag", default="change")
    ints = lambda s: [int(x) for x in s.split(",")]     # noqa: E731
    ap.add_argument("--pieces", type=ints)
    ap.add_argument("--depths", type=ints)
    ap.add_argument("--ways", type=lambda s: s.split(","),
                    default=["as", "dma", "body"])
    ap.add_argument("--lives", type=ints)
    ap.add_argument("--save")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("kernels", nargs="*")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    if args.rehearse:
        global REHEARSE
        REHEARSE = True
        SHAPES.update({
            "ssm_update": ((3, 6, 8, 8, 128), 128, (2,)),
            "kda_update": ((3, 6, 4, 16, 128), 128, (3,)),
            "delta_update": ((3, 6, 6, 8, 256), 192, (1,))})
    out = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    log = open(os.path.join(out, f"time_state_update.{args.tag}.jsonl"), "a")

    def say(**row):
        row = dict(tree=args.tag, **row)
        print(json.dumps(row), flush=True)
        log.write(json.dumps(row) + "\n")
        log.flush()

    names = args.kernels or list(SHAPES)
    for name in names:
        time_kernel(name, args, say)
    if args.save:
        kept = {}
        for name in names:
            kept.update(record(name))
        np.savez(args.save, **kept)
    return 0


if __name__ == "__main__":
    sys.exit(main())
