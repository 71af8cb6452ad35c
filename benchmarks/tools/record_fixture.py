"""Record the small TPU trace the yardstick's tests read
(``benchmarks/tests/fixtures/named_flash_tpu.xplane.pb``), on the chip:

    chiprun -- python3 benchmarks/tools/record_fixture.py chiprun_out

Three runs of one jitted ``step`` — a 4-step scan of matmuls, the
program's flash attention forward and backward (the three kernels carry
their names), a ``head_loss`` scope over a reduction and an
``optimizer`` scope over an elementwise update — each launched under
the program's ``device.annotation("train.step")``, which carries the
host clock, with a 2 ms sleep under ``serve.harvest_chunk`` between
them.  Beside the trace it writes ``named_flash_tpu.txt``: every distinct
op of the device with ALL the stats ``ProfileData`` shows for it — the
place to look for where a scope name lands (PERF.md section 3).
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from ray_tpu.observability import device
    from ray_tpu.ops.flash_attention import flash_attention

    if jax.devices()[0].platform != "tpu":
        print("record_fixture.py: needs the chip", file=sys.stderr)
        return 2

    def loss(w, x):
        h, _ = jax.lax.scan(lambda h, wi: (jnp.tanh(h @ wi), None), x, w)
        q = h.reshape(1, 256, 2, 128)
        o = flash_attention(q, q[:, :, :1], q[:, :, :1])
        with jax.named_scope("head_loss"):
            return jnp.mean(jax.nn.logsumexp(
                o.astype(jnp.float32), axis=-1))

    @jax.jit
    def step(w, x):
        value, grads = jax.value_and_grad(jax.checkpoint(loss))(w, x)
        with jax.named_scope("optimizer"):
            w = (w.astype(jnp.float32)
                 - 1e-3 * grads.astype(jnp.float32)).astype(w.dtype)
        return w, value

    w = jax.random.normal(jax.random.key(0), (4, 256, 256), jnp.bfloat16)
    x = jax.random.normal(jax.random.key(1), (256, 256), jnp.bfloat16)
    w, _ = step(w, x)
    jax.block_until_ready(w)
    trace_dir = os.path.join(out_dir, "named_flash_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(3):
        with device.annotation("train.step"):
            w, value = step(w, x)
        with device.annotation("serve.harvest_chunk"):
            jax.block_until_ready(value)
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    kept = os.path.join(out_dir, "named_flash_tpu.xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(trace_dir)
    seen, lines = set(), []
    for plane in ProfileData.from_file(kept).planes:
        for line in plane.lines:
            for ev in line.events:
                key = (plane.name, line.name, ev.name.split(" = ")[0])
                if key in seen or not (plane.name.startswith("/device")
                                       or "#" in ev.name):
                    continue
                seen.add(key)
                lines.append(f"{plane.name} | {line.name} | {ev.name[:300]}"
                             f"\n    stats: "
                             f"{[(k, str(v)[:200]) for k, v in ev.stats]}")
    with open(os.path.join(out_dir, "named_flash_tpu.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {os.path.getsize(kept)} bytes, {len(lines)} distinct events")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out"))
