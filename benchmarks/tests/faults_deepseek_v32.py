"""The controls of ``deepseek-v3.2-exp.serve-longdoc``'s comparison: the
faults that the cell's three limits must read as NOT correct, planted on the
program's side (never shipped) or, for fp8, on the reference's, and read
through the runner's own functions (``serve_open_loop_median.answer_gaps`` /
``readings`` / ``within``: ``faults_olmo_hybrid.py``'s ``served`` and
``read``, which name no family). ``plant`` is what
``test_rehearsal_deepseek_v32.py`` plants at toy size on the CPU; on the
chip (the readings in the cell file's ``notes``), one process a variant for
the engine, then one for the reference:

    python3 benchmarks/tests/faults_deepseek_v32.py serve <variant> <seed> ..
    python3 benchmarks/tests/faults_deepseek_v32.py check <variant> ..
    python3 benchmarks/tests/faults_deepseek_v32.py check_fp8 sound

``serve`` writes ``chiprun_out/faults57_<variant>.json`` (prompt, answer and
the engine's log-probabilities of each check request), ``check`` prints one
line a variant and seed and appends it to
``chiprun_out/faults57_readings.jsonl``.

The variants: ``recent`` (the indexer's choice replaced by the ``index_topk``
most recent positions: a sliding window), ``ik_unrotated`` (the indexer's key
enters its cache without its rotation, the index queries rotated as they
should be), ``no_relu`` (the index score a plain weighted sum of products),
``no_mscale`` (the softmax scale without YaRN's ``m^2``), ``no_yarn`` (the
rotations at plain ``theta``, YaRN's ramp left out; the scale as it should
be). Every one leaves prompts of up to ``index_topk`` tokens nearly or wholly
as they were but for the last two.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepseek-v3.2-exp.serve-longdoc"
VARIANTS = ("recent", "ik_unrotated", "no_relu", "no_mscale", "no_yarn")
MATRICES = ("w_dq", "w_uq", "w_dkv", "w_ukv", "wo", "w_iq", "w_ik", "w_iw",
            "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
            "shared_down", "expert_fc", "expert_gate", "expert_out", "wte",
            "lm_head")


def plant(variant, config, setattr=setattr):
    """One fault in the program this process will build from ``config``
    (``sound``: none). ``setattr``: a test's ``monkeypatch.setattr``."""
    import jax.numpy as jnp

    from ray_tpu.models import bailing_hybrid, deepseek_v32
    from ray_tpu.ops import index_select

    if variant == "sound":
        return
    if variant == "recent":
        def most_recent(scores, visible, kept):
            # ``visible`` is a prefix of the positions: its last ``kept``
            first = visible.sum(-1, keepdims=True) - kept
            return visible & (jnp.arange(visible.shape[-1]) >= first)
        setattr(index_select, "chosen", most_recent)
    elif variant == "ik_unrotated":
        indexed = deepseek_v32._indexed

        def key_as_projected(cfg, layer, h, cq, pos):
            # position 0 rotates nothing
            return indexed(cfg, layer, h, cq, pos)._replace(
                key=indexed(cfg, layer, h, cq, jnp.zeros_like(pos)).key)
        setattr(deepseek_v32, "_indexed", key_as_projected)
    elif variant == "no_relu":
        setattr(index_select, "_weighted",
                lambda weights, s: (weights[..., None] * s).sum(-2))
    elif variant == "no_mscale":
        config["model"]["rope_mscale_all_dim"] = 0     # m = 1
    elif variant == "no_yarn":
        frequencies = bailing_hybrid._inv_freq

        def plain(D, theta, yarn=None):
            return frequencies(D, theta)
        setattr(bailing_hybrid, "_inv_freq", plain)
        setattr(deepseek_v32, "_inv_freq", plain)
    else:
        raise SystemExit(f"unknown variant {variant}")


def fp8(params):
    """Every matrix rounded to e4m3 with one scale a matrix (a layer's, and
    an expert's, of a stacked leaf): the precision below the configuration's
    bf16, on the REFERENCE's side."""
    import jax
    import jax.numpy as jnp

    def rounded(key, a):
        if key not in MATRICES:
            return a
        lead = {"wte": 0, "lm_head": 0, "expert_fc": 2, "expert_gate": 2,
                "expert_out": 2}.get(key, 1)

        def one(x):
            x = x.astype(jnp.float32)
            axes = tuple(range(max(lead - 1, 0), x.ndim))
            scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
            return ((x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                    * scale).astype(a.dtype)

        # a layer at a time: a stack of experts in float32 is 3.8 GB
        return one(a) if lead == 0 else jnp.stack([one(x) for x in a])

    # eagerly, leaf by leaf (inside one jit XLA drops a float32 -> fp8 ->
    # float32 round trip), and each leaf given up as its rounding is made:
    # two trees of 9.3 GB do not fit the chip
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
        with open(os.path.join(out, f"faults57_{variant}.json"), "w") as f:
            json.dump(served(config, mix, seeds), f)
        return
    params = reference.program_initial_weights(config)
    if how == "check_fp8":
        params = fp8(params)
    for variant in rest:
        with open(os.path.join(out, f"faults57_{variant}.json")) as f:
            samples = json.load(f)
        for seed, sample in samples.items():
            row = {"variant": variant + ("|reference_fp8"
                                         if how == "check_fp8" else ""),
                   "seed": int(seed), **read(config, mix, sample, params)}
            print(json.dumps(row), flush=True)
            with open(os.path.join(out, "faults57_readings.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(*sys.argv[1:])
