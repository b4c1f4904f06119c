"""Records ``data/v5e_1chip_spans.xplane.pb`` on the chip
(``chiprun -- python3 benchmarks/tests/record_hot_path_trace.py <out.pb>``):
one capture of the program's own loops at toy size, in this process — a
two-slot decode engine answering four requests (three through
``LLMServer``, one of them streamed; one handed to the engine directly),
then four steps of ``default_jax_train_loop`` (the first compiles). Python
tracer off and host tracer at level 1, and the ``/host:metadata`` plane (the
programs' HLO protos, 1.5 of 2.3 MB, which no reader uses) is left out of
the copy, so that the file stays small: it keeps the program's
``TraceAnnotation`` spans and the runtime's ``DoEnqueueProgram`` events,
whose ``run_id`` places the chip's clock on the host's. Kept so the recorded
file has a provenance; no test runs it."""
import glob
import os
import sys
import tempfile
import threading
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def serve(srv) -> None:
    from ray_tpu.llm import SamplingParams

    threads = [
        threading.Thread(target=srv.completions, args=(
            {"prompt": "hi", "max_tokens": 6},)),
        threading.Thread(target=srv.completions, args=(
            {"prompt": "hello there, " * 2, "max_tokens": 4},)),
        threading.Thread(target=lambda: list(srv.completions_stream(
            {"prompt": "what is", "max_tokens": 4}))),
        threading.Thread(target=lambda: srv.engine.submit(
            srv.engine.tokenizer.encode("zzzz"),
            SamplingParams(max_new_tokens=3)).result(120)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)


def train(run_dir: str) -> None:
    from ray_tpu.train.context import TrainContext, _set_context
    from ray_tpu.train.trainer import default_jax_train_loop

    def work():
        _set_context(TrainContext(0, 1, 0, 1, 0, "record", run_dir))
        default_jax_train_loop({
            "model": dict(vocab_size=512, max_seq_len=128, num_layers=1,
                          num_heads=2, embed_dim=128, attention_impl="xla"),
            "mesh": {"data": -1}, "num_steps": 4, "batch_size": 8,
            "seq_len": 128, "checkpoint_every": 0,
        })

    t = threading.Thread(target=work, name="train-loop")
    t.start()
    t.join(600)


def without_plane(xspace: bytes, name: str) -> bytes:
    """``xspace`` (a serialized XSpace: field 1 is ``repeated XPlane
    planes``, and an XPlane's field 2 its name) less the plane ``name``."""
    def varint(at):
        value = shift = 0
        while True:
            byte = xspace[at]
            at += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if not byte & 0x80:
                return value, at

    out, at = bytearray(), 0
    while at < len(xspace):
        start = at
        key, at = varint(at)
        assert key & 7 == 2, "an XSpace holds length-delimited fields only"
        size, at = varint(at)
        at += size
        if not (key >> 3 == 1 and b"\x12" + bytes([len(name)])
                + name.encode() in xspace[at - size:at - size + 64]):
            out += xspace[start:at]
    return bytes(out)


def main(out_path: str) -> None:
    import jax

    from ray_tpu.llm import DecodeEngine, LLMConfig
    from ray_tpu.llm.serving import LLMServer

    assert jax.devices()[0].platform == "tpu", jax.devices()
    srv = LLMServer.__new__(LLMServer)
    srv.config = LLMConfig(
        vocab_size=512, max_seq_len=128, num_layers=1, num_heads=2,
        embed_dim=128, max_batch_slots=2, prefill_buckets=(16, 32))
    srv.engine = DecodeEngine(srv.config, seed=0)
    work = tempfile.mkdtemp(prefix="hot_path_trace_")
    serve(srv)  # compiles both buckets, insert and decode
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(
        os.path.join(work, "trace"), profiler_options=options)
    time.sleep(0.05)  # the idle engine
    serve(srv)
    time.sleep(0.05)
    srv.engine.shutdown()  # or it idles through the train loop's compile
    train(os.path.join(work, "run"))
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(work, "trace", "**", "*.xplane.pb"),
                      recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(found[0], "rb") as f, open(out_path, "wb") as out:
        out.write(without_plane(f.read(), "/host:metadata"))
    print(out_path, os.path.getsize(out_path), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
