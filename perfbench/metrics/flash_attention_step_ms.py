"""Layer: kernels. Device time of the flash-attention kernels in one
training step: the share of the traced window's busy device time that
the ``breakdown.device_ops`` entries naming a ``flash_*`` kernel take
(``ops/flash_attention.py``'s ``name=``: ``flash_fwd``, ``flash_bwd_fused``
or ``flash_bwd_dq`` + ``flash_bwd_dkv``), times the step program's median
device time. (Not over ``counters["traced_steps"]``: that counts the
batches handed out after the trace began, 9 where the device ran 10.9
steps' worth.)

``device_ops`` is TRUNCATED to the ten costliest operations of the trace,
so a kernel that falls out of the ten is not seen at all. A partial sum
never passes for the whole: the value is ``None`` unless the forward
(``flash_fwd``) AND a backward (``flash_bwd*``) kernel are both among the
ten, as it is on a program whose kernels have no name. Of a split
backward (``dq`` + ``dkv``) one half may still be missing: a sum by name
over the whole trace needs ``harness/tracing.py`` to give one.
Source: device_trace."""

import statistics


def read(outcome):
    tr = outcome["trace"]
    ops = tr["device_ops"]
    if not (any("flash_fwd" in label for label, _ in ops)
            and any("flash_bwd" in label for label, _ in ops)):
        return None
    if not tr["modules"] or not tr["busy_s"]:
        return None
    flash = sum(s for label, s in ops if "flash_" in label)
    step_s = statistics.median(max(tr["modules"].values(), key=sum))
    return 1e3 * step_s * flash / tr["busy_s"]
