"""How ``tests/data/small.xplane.pb`` was recorded (on the chip, once):

    chiprun --chips 1 -- python3 benchmark/tests/record_trace.py

Three steps of a small jitted training-like function (matmuls, the
flash-attention kernels forward and backward) with a host sleep between
them, under the same host annotations the harness writes. Prints what
the trace holds (planes, lines, event and stat names), so that
``trace_reduce.py`` can be written against the real thing, and leaves
the ``.xplane.pb`` and the compiled HLO text under ``chiprun_out/``.
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas_attention import flash_attention


def small_step(w, x, q):
    def loss(w, q):
        h = jnp.tanh(x @ w)
        attn = flash_attention(q, q, q, causal=True)
        return jnp.mean((h @ w.T) ** 2) + jnp.mean(attn.astype(jnp.float32))

    value, (dw, dq) = jax.value_and_grad(loss, (0, 1))(w, q)
    return w - 0.1 * dw, q - (0.1 * dq).astype(q.dtype), value


def main():
    out = os.path.join(ROOT, "chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    print("devices:", jax.devices())
    w = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    x = jnp.ones((256, 512), jnp.bfloat16)
    q = jnp.ones((1, 256, 2, 64), jnp.bfloat16) * 0.1
    compiled = jax.jit(small_step).lower(w, x, q).compile()
    with open(os.path.join(out, "small_step.hlo.txt"), "w") as f:
        f.write(compiled.as_text())
    w, q, value = compiled(w, x, q)
    jax.block_until_ready(value)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("window_edge"):
        jax.block_until_ready(w)
    for i in range(3):
        with jax.profiler.TraceAnnotation("dispatch", step=i):
            w, q, value = compiled(w, x, q)
        with jax.profiler.TraceAnnotation("loss_fetch", step=i):
            jax.block_until_ready(value)
        time.sleep(0.002)
    jax.profiler.stop_trace()

    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(os.path.join(out, "plugins"))
    print("xplane bytes:", os.path.getsize(os.path.join(out,
                                                        "small.xplane.pb")))
    data = jax.profiler.ProfileData.from_file(
        os.path.join(out, "small.xplane.pb"))
    for plane in data.planes:
        lines = list(plane.lines)
        print("PLANE %r: %d lines" % (plane.name, len(lines)))
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            print("  LINE %r: %d events" % (line.name, len(events)))
            shown = set()
            for ev in events:
                if ev.name in shown or len(shown) >= 12:
                    continue
                shown.add(ev.name)
                stats = {k: (v if not isinstance(v, (str, bytes))
                             else str(v)[:160]) for k, v in ev.stats}
                print("    %r start_ns=%s dur_ns=%s stats=%r"
                      % (ev.name[:120], ev.start_ns, ev.duration_ns, stats))


if __name__ == "__main__":
    main()
