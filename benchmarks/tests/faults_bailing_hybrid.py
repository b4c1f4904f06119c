"""The controls of ``ling-3.0-flash.serve-longgen``'s comparison: the faults
that the cell's three limits must read as NOT correct, planted on the
program's side (never shipped) or, for fp8, on the reference's, and read
through the runner's own functions (``serve_open_loop_median.answer_gaps`` /
``readings`` / ``within``: ``faults_olmo_hybrid.py``'s ``served`` and
``read``, which name no family). ``plant`` is what
``test_rehearsal_bailing_hybrid.py`` plants at toy size on the CPU; on the
chip (the readings in the cell file's ``notes``), one process a variant for
the engine, then one for the reference:

    python3 benchmarks/tests/faults_bailing_hybrid.py serve <variant> <seed> ..
    python3 benchmarks/tests/faults_bailing_hybrid.py check <variant> ..
    python3 benchmarks/tests/faults_bailing_hybrid.py check_fp8 sound

``serve`` writes ``chiprun_out/faults51_<variant>.json`` (prompt, answer and
the engine's log-probabilities of each check request), ``check`` prints one
line a variant and seed and appends it to
``chiprun_out/faults51_readings.jsonl``.

The variants: ``state_bf16`` (a KDA layer's matrices held in bfloat16's
precision), ``decay_head_mean`` (the decay's channel vector replaced by its
head's mean: Gated DeltaNet's scalar where KDA has a vector), ``kr_unrotated``
(the shared key enters the latent cache without its rotation, the queries
rotated as they should be).
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ling-3.0-flash.serve-longgen"
VARIANTS = ("state_bf16", "decay_head_mean", "kr_unrotated")
MATRICES = ("kda_in", "kda_f", "kda_gates", "kda_out", "wq", "w_dkv",
            "w_ukv", "wz", "wo", "w_gate", "w_up", "w_down", "shared_gate",
            "shared_up", "shared_down", "expert_fc", "expert_gate",
            "expert_out", "wte", "lm_head")


def plant(variant, config, setattr=setattr):
    """One fault in the program this process will build from ``config``
    (``sound``: none). ``setattr``: a test's ``monkeypatch.setattr``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import bailing_hybrid, kv_cache

    if variant == "sound":
        return
    if variant == "state_bf16":
        recur = kv_cache.recur

        def held_narrow(carried, *a):
            cache, y = recur(carried, *a)
            # not astype(bfloat16).astype(float32): the compiler may drop
            # that round trip (excess precision is allowed)
            return {**cache, "ssm": jax.lax.reduce_precision(
                cache["ssm"], exponent_bits=8, mantissa_bits=7)}, y
        setattr(kv_cache, "recur", held_narrow)
    elif variant == "decay_head_mean":
        state_in = bailing_hybrid.state_in

        def one_decay_a_head(cfg, kind, layer, x):
            entering, (g, beta), kept = state_in(cfg, kind, layer, x)
            g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
            return entering, (g, beta), kept
        setattr(bailing_hybrid, "state_in", one_decay_a_head)
    elif variant == "kr_unrotated":
        qkv = bailing_hybrid.qkv

        def key_as_projected(cfg, kind, layer, x, pos):
            q, _, up = qkv(cfg, kind, layer, x, pos)
            # position 0 rotates nothing
            _, rows, _ = qkv(cfg, kind, layer, x, jnp.zeros_like(pos))
            return q, rows, up
        setattr(bailing_hybrid, "qkv", key_as_projected)
    else:
        raise SystemExit(f"unknown variant {variant}")

def fp8(params):
    """Every matrix rounded to e4m3 with one scale a matrix (a layer's, of a
    stacked leaf): the precision below the configuration's bf16, on the
    REFERENCE's side."""
    import jax
    import jax.numpy as jnp

    def rounded(key, a):
        if key not in MATRICES:
            return a
        # one scale a matrix: a layer's, and an expert's, of a stacked leaf
        lead = {"wte": 0, "lm_head": 0, "expert_fc": 2, "expert_gate": 2,
                "expert_out": 2}.get(key, 1)

        def one(x):
            x = x.astype(jnp.float32)
            axes = tuple(range(max(lead - 1, 0), x.ndim))
            scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
            return ((x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                    * scale).astype(a.dtype)

        # a layer at a time: a stack of experts in float32 is 5 GB
        return one(a) if lead == 0 else jnp.stack([one(x) for x in a])

    # eagerly, leaf by leaf (inside one jit XLA drops a float32 -> fp8 ->
    # float32 round trip), and each leaf given up as its rounding is made:
    # two trees of 8.7 GB do not fit the chip
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    del params
    out = []
    while leaves:
        path, a = leaves.pop(0)
        b = rounded(path[-1].key, a)
        if b is not a:
            b.block_until_ready()
            a.delete()
        out.append(b)
    return jax.tree_util.tree_unflatten(tree, out)


def main(how, *rest):
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmarks import run as harness
    from benchmarks.lib import reference
    from benchmarks.tests.faults_olmo_hybrid import read, served

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    _, cell, config, _, _ = harness.load_cell(CELL)
    mix = cell["traffic"]
    if how == "serve":
        variant, seeds = rest[0], [int(s) for s in rest[1:]]
        plant(variant, config)
        with open(os.path.join(out, f"faults51_{variant}.json"), "w") as f:
            json.dump(served(config, mix, seeds), f)
        return
    params = reference.program_initial_weights(config)
    if how == "check_fp8":
        params = fp8(params)
    for variant in rest:
        with open(os.path.join(out, f"faults51_{variant}.json")) as f:
            samples = json.load(f)
        for seed, sample in samples.items():
            row = {"variant": variant + ("|reference_fp8"
                                         if how == "check_fp8" else ""),
                   "seed": int(seed), **read(config, mix, sample, params)}
            print(json.dumps(row), flush=True)
            with open(os.path.join(out, "faults51_readings.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(*sys.argv[1:])
