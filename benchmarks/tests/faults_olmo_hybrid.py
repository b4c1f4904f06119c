"""The controls of ``olmo-hybrid-7b.serve-docs``'s comparison: the faults that
the cell's three limits must read as NOT correct, planted on the program's
side (never shipped) or, for fp8, on the reference's, and read through the
runner's own functions (``serve_open_loop_median.answer_gaps`` / ``readings``
/ ``within``). ``plant`` is what ``test_rehearsal_olmo_hybrid.py`` plants at
toy size on the CPU; on the chip (the readings in the cell file's ``notes``),
one process a variant for the engine, then one for the reference:

    python3 benchmarks/tests/faults_olmo_hybrid.py serve <variant> <seed> ..
    python3 benchmarks/tests/faults_olmo_hybrid.py check <variant> ..
    python3 benchmarks/tests/faults_olmo_hybrid.py check_fp8 sound

``serve`` writes ``chiprun_out/faults47_<variant>.json`` (prompt, answer and
the engine's log-probabilities of each check request), ``check`` prints one
line a variant and seed and appends it to
``chiprun_out/faults47_readings.jsonl``.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "olmo-hybrid-7b.serve-docs"
VARIANTS = ("state_bf16", "state_zeroed", "conv_dropped", "pad_writes",
            "beta1", "gate_first")
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "delta_in",
            "delta_gates", "delta_out", "wte", "lm_head")


def plant(variant, config, setattr=setattr):
    """One fault in the program this process will build from ``config``
    (``sound``: none). ``setattr``: a test's ``monkeypatch.setattr``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import kv_cache, olmo_hybrid

    recur = kv_cache.recur
    if variant == "sound":
        return
    if variant == "state_bf16":
        def held_narrow(carried, *a):
            cache, y = recur(carried, *a)
            # not astype(bfloat16).astype(float32): the compiler may drop
            # that round trip (excess precision is allowed)
            return {**cache, "ssm": jax.lax.reduce_precision(
                cache["ssm"], exponent_bits=8, mantissa_bits=7)}, y
        setattr(kv_cache, "recur", held_narrow)
    elif variant in ("state_zeroed", "conv_dropped"):
        leaf = "ssm" if variant == "state_zeroed" else "conv"

        def at_a_chunk_boundary(carried, entering, *a):
            cache, index, at = carried
            if entering.shape[1] > 1:   # a chunk starts from nothing
                cache = {**cache, leaf: jnp.zeros_like(cache[leaf])}
            return recur((cache, index, at), entering, *a)
        setattr(kv_cache, "recur", at_a_chunk_boundary)
    elif variant == "pad_writes":
        def every_step_a_token(carried, *a):
            cache, index, at = carried
            return recur((cache, index, at._replace(real=None)), *a)
        setattr(kv_cache, "recur", every_step_a_token)
    elif variant == "beta1":
        config["model"]["linear_allow_neg_eigval"] = False
    elif variant == "gate_first":
        def gate_first(cfg, layer, x, y, gate):
            y = y * jax.nn.silu(gate.astype(jnp.float32)).reshape(y.shape)
            y = olmo_hybrid._rms_norm(y, layer["gate_norm"], cfg.rms_eps)
            y = y.reshape(*y.shape[:2], -1).astype(cfg.dtype)
            out = jnp.einsum("btf,fe->bte", y,
                             layer["delta_out"].astype(y.dtype))
            return olmo_hybrid._branch(cfg, x, out, layer["mix_norm"])
        setattr(olmo_hybrid, "state_out", gate_first)
    else:
        raise SystemExit(f"unknown variant {variant}")


def served(config, mix, seeds):
    """{seed: [(prompt, answer, the engine's log-probabilities)]} of the
    cell's check requests, through ``DecodeEngine`` alone (no proxy)."""
    import numpy as np

    from benchmarks.lib import program
    from ray_tpu.llm.engine import DecodeEngine, SamplingParams

    engine = DecodeEngine(program.llm_config(config))
    try:
        asked = []
        for seed in seeds:
            rng = np.random.default_rng(seed)
            for n in mix["check_prompt_tokens"]:
                prompt = [int(c) + 2 for c in rng.integers(97, 123, int(n))]
                asked.append((seed, prompt, engine.submit(
                    prompt, SamplingParams(
                        max_new_tokens=int(mix["check_max_tokens"]),
                        logprobs=1))))
        out = {}
        for seed, prompt, future in asked:
            answer = future.result(timeout=1200)
            out.setdefault(str(seed), []).append(
                (prompt, list(answer), [e["logprob"] for e in answer.logprobs]))
        return out
    finally:
        engine.shutdown()


def read(config, mix, sample, params=None):
    """The runner's three readings of one seed's ``sample`` against the
    reference (over ``params``, or the program's own initial weights), and
    whether they lie ``within`` the cell's limits."""
    from benchmarks.lib import reference
    from benchmarks.runners import serve_open_loop_median as runner

    if params is None:
        params = reference.program_initial_weights(config)
    gaps = runner.answer_gaps(reference.logits_of(config), params,
                              [tuple(s) for s in sample],
                              int(mix["check_pad_to"]))
    got = runner.readings(gaps)
    return {**got, "within": runner.within(got, [], mix)}


def fp8(params):
    """Every matrix rounded to e4m3 with one scale a matrix (a layer's, of a
    stacked leaf): the precision below the configuration's bf16, on the
    REFERENCE's side."""
    import jax
    import jax.numpy as jnp

    def rounded(key, a):
        if key not in MATRICES:
            return a
        x = a.astype(jnp.float32)
        stacked = key not in ("wte", "lm_head")
        axes = tuple(range(1 if stacked else 0, a.ndim))
        scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
        return ((x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * scale).astype(a.dtype)

    # eagerly, leaf by leaf (inside one jit XLA drops a float32 -> fp8 ->
    # float32 round trip), and each leaf given up as its rounding is made:
    # two trees of 8.2 GB do not fit the chip
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

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    _, cell, config, _, _ = harness.load_cell(CELL)
    mix = cell["traffic"]
    if how == "serve":
        variant, seeds = rest[0], [int(s) for s in rest[1:]]
        plant(variant, config)
        with open(os.path.join(out, f"faults47_{variant}.json"), "w") as f:
            json.dump(served(config, mix, seeds), f)
        return
    params = reference.program_initial_weights(config)
    if how == "check_fp8":
        params = fp8(params)
    for variant in rest:
        with open(os.path.join(out, f"faults47_{variant}.json")) as f:
            samples = json.load(f)
        for seed, sample in samples.items():
            row = {"variant": variant + ("|reference_fp8"
                                         if how == "check_fp8" else ""),
                   "seed": int(seed), **read(config, mix, sample, params)}
            print(json.dumps(row), flush=True)
            with open(os.path.join(out, "faults47_readings.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(*sys.argv[1:])
