#!/usr/bin/env python3
"""Record the small trace that the trace-reduction tests read.

    python3 bench/tests/data/record_trace.py OUT_DIR

On a TPU: two "rounds" inside the harness's window span, each one call of
the program's batched Eq. 2 over a tiny two-leaf model and one flash KD
forward and backward at (256, 10), with a host sleep between the rounds.
Writes the profiler's files under OUT_DIR and prints every device
operation's name.
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro.kernels.kd_loss import ops as kd_ops
    from repro.kernels.weight_avg import ops as w_ops

    import device_trace

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    tree = {"a": jax.random.normal(k[0], (4, 2, 3, 3, 16, 16)),
            "b": jax.random.normal(k[1], (4, 2, 10))}
    w = jnp.ones((4, 2), jnp.float32)
    s = jax.random.normal(k[2], (256, 10))
    zt = jax.random.normal(k[3], (256, 10)).astype(jnp.bfloat16)
    lse = kd_ops.teacher_cache_lse(zt, 4.0)
    kd = jax.jit(jax.value_and_grad(
        lambda x: kd_ops.flash_kd_loss(x, zt, 4.0, teacher_lse=lse)))
    avg = w_ops.group_weighted_average_pytree

    def step():
        out = avg(tree, w)
        loss, g = kd(s)
        jax.block_until_ready((out, loss, g))

    step()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation(device_trace.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(device_trace.ROUND_SPAN):
                step()
            time.sleep(0.05)
    jax.profiler.stop_trace()
    summary = device_trace.summarize_dir(out_dir)
    for name in sorted(summary.op_ns):
        print(f"op {name!r} {summary.op_count[name]} "
              f"{summary.op_ns[name]} ns")
    print(f"busy {summary.busy_s} s of {summary.window_s} s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
