"""Device time of the jitted forward outside the class-sums kernel, over
the forward's device time (%): the per-dispatch operand rebuilds (the
deviation stack's re-pad among them) and the ensemble vote."""

from readings import FORWARD_MODULE, KERNEL_OP


def read(run):
    t = run.trace
    if t is None:
        return None
    step = sum(v for k, v in t.module_s.items()
               if k.startswith(FORWARD_MODULE))
    if step <= 0:
        return None
    kernel = sum(v for k, v in t.op_s.items() if k.startswith(KERNEL_OP))
    return 100.0 * (step - kernel) / step
