"""Shared example bootstrap: honor --device cpu / --device=cpu BEFORE any
jax backend use (same effect as JAX_PLATFORMS=cpu in the environment)."""
import sys


def maybe_force_cpu(argv=None):
    argv = sys.argv if argv is None else argv
    i = argv.index("--device") if "--device" in argv else -1
    if "--device=cpu" in argv or (i >= 0 and argv[i + 1:i + 2] == ["cpu"]):
        import jax
        jax.config.update("jax_platforms", "cpu")
        # pure_callback custom ops (e.g. train_rcnn's proposal/target ops)
        # re-enter jax from the callback thread; with async CPU dispatch
        # that deadlocks on thread-pool starvation when cores are scarce.
        # Must be set before the CPU client exists.
        jax.config.update("jax_cpu_enable_async_dispatch", False)


def pick_ctx():
    """mx.cpu() where the user pinned the process to the CPU (--device cpu
    or JAX_PLATFORMS=cpu), else mx.tpu() — which raises when JAX finds no
    chip, so an example never trains on another device than the one asked
    for. The choice goes to stderr."""
    import mxnet_tpu as mx
    ctx = mx.cpu() if mx.context.cpu_pinned() else mx.tpu()
    print("device: %s -> %s" % (ctx, ctx.jax_device), file=sys.stderr,
          flush=True)
    return ctx


def check_improved(metric_name, values, lower_is_better=True):
    """Exit nonzero when a multi-epoch run did not improve; a single
    epoch can't self-compare and just reports the value."""
    if len(values) < 2:
        return
    ok = values[-1] < values[0] if lower_is_better else         values[-1] > values[0]
    if not ok:
        raise SystemExit("%s did not improve: %s" % (metric_name, values))
