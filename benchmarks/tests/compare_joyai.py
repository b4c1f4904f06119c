"""PR 55's chip comparison, by the builder (not the runner): the program's
two losses on the timed batch against the reference's ``loss_parts``, and
its gradients at the published widths on 1 x 2048 tokens against the
reference's, a leaf at a time, sound and with each fault of
``tests/test_joyai_llm_flash.py:FAULTS`` planted on the program's side.

    python3 benchmarks/tests/compare_joyai.py losses <seed> ...
    python3 benchmarks/tests/compare_joyai.py grads <seed> [fault | all]
"""
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import named, program, reference
from benchmarks.runners.train_job import first_batch
from ray_tpu.models import config_for, module_for

CELL = os.environ.get("CELL55", "joyai-llm-flash.train-seq8k")
ROOT = os.environ.get("ROOT55", run.CHECKOUT)
TOKENS = int(os.environ.get("TOKENS55", 2048))
OUT = "chiprun_out"


def setup():
    _, cell, config, _, _ = run.load_cell(CELL, ROOT)
    model = program.trainer_model(config)
    cfg = config_for(model.pop("family"), **model)
    ref = named.load(config["files"]["reference"])
    params = reference.program_initial_weights(config)
    return cell, config, cfg, ref, params


def losses(seeds):
    cell, config, cfg, ref, params = setup()
    fam = module_for(cfg)
    job = cell["job"]
    prog = jax.jit(lambda p, t: fam.loss_fn(p, {"tokens": t}, cfg, parts=True))
    with jax.default_matmul_precision("highest"):
        parts = jax.jit(ref.loss_parts)
    rows = []
    for seed in seeds:
        toks = first_batch(seed, cfg.vocab_size, job["batch_size"],
                           job["seq_len"])
        xent, aux = prog(params, jnp.asarray(toks))
        got = (float(xent), float(aux["mtp_loss"]))
        with jax.default_matmul_precision("highest"):
            per = [tuple(float(x) for x in parts(params, jnp.asarray(
                toks[i:i + 1]))) for i in range(len(toks))]
        want = tuple(np.mean([p[j] for p in per]) for j in (0, 1))
        rows.append({"seed": seed, "main": got[0], "main_ref": want[0],
                     "mtp": got[1], "mtp_ref": want[1],
                     "main_gap": abs(got[0] - want[0]),
                     "mtp_gap": abs(got[1] - want[1])})
        print(json.dumps(rows[-1]), flush=True)
    with open(os.path.join(OUT, "p55_losses.json"), "w") as f:
        json.dump(rows, f, indent=1)


def grads(seed, which):
    import tests.test_joyai_llm_flash as t

    cell, config, cfg, ref, params = setup()
    fam = module_for(cfg)
    toks = jnp.asarray(first_batch(seed, cfg.vocab_size, 1, TOKENS))
    # the reference's file stays plain; HERE each of its layers is run again
    # in the backward pass (the same float32 arithmetic twice): without it
    # the heads' [T, T] scores of six layers are 9.7 GB of residuals beside
    # the weights and their gradients, and the chip refuses the program
    ref._layer = jax.checkpoint(ref._layer)
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(jax.grad(ref.loss)).lower(params, toks).compile()
        mem = compiled.memory_analysis()
        print("reference gradients: arguments", mem.argument_size_in_bytes,
              "temporaries", mem.temp_size_in_bytes, "tokens", toks.shape,
              flush=True)
        want = jax.device_get(compiled(params, toks))
    print("reference gradients made", flush=True)
    names = ["sound"] + (list(t.FAULTS) if which == "all" else
                         [which] if which else [])
    table = {}
    for name in names:
        changes = {
            "routed_scaling_factor left out": {"moe": dataclasses.replace(
                cfg.moe, route_scale=1.0)},
            "loss weight 0": {"mtp_loss_weight": 0.0}}.get(name, {})
        c = dataclasses.replace(cfg, **changes)
        mp = pytest.MonkeyPatch()
        if name != "sound" and t.FAULTS[name] is not None:
            t.FAULTS[name](mp, c)
        t0 = time.time()
        try:
            got = jax.device_get(jax.jit(jax.grad(
                lambda p, tk: fam.loss_fn(p, {"tokens": tk}, c)))(params, toks))
        finally:
            mp.undo()
        rows = t.leaf_gaps(got, want)
        table[name] = rows
        worst_n = max(rows.items(), key=lambda kv: kv[1][0])
        worst_t = max(rows.items(), key=lambda kv: kv[1][1])
        print(json.dumps({"variant": name, "seconds": time.time() - t0,
                          "worst_norm": worst_n, "worst_turn": worst_t}),
              flush=True)
    with open(os.path.join(OUT, f"p55_grads_{seed}.json"), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    os.makedirs(OUT, exist_ok=True)
    if sys.argv[1] == "losses":
        losses([int(s) for s in sys.argv[2:]])
    else:
        grads(int(sys.argv[2]), sys.argv[3] if len(sys.argv) > 3 else None)
