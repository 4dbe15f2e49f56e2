"""Layer: kernels. Share of the traced window's busy device time that the
fused paged-attention kernel takes, in percent: the
``breakdown.device_ops`` entries naming ``paged_decode_attn``
(``ops/paged_flash.py``'s ``name=``: the decode tick's read of the K/V
pool through the block tables on a TPU, and a chunk program's where a
server names the spelling) over ``busy_s``. Lower is better: the kernel
reads live blocks only, so what is left of its share is grid steps and
DMAs that a later kernel can merge.

``device_ops`` is TRUNCATED to the ten costliest operations of the trace:
a kernel shape that falls out of the ten (a narrow chunk bucket's) is not
counted, so the value is the share of the shapes that are there. ``None``
where no entry names the kernel: a program that reads K/V through the
dense gather (the parent of the PR that made the kernel the tick's read
on a TPU), as ``flash_attention_step_ms`` does on a program without its
kernels. Source: device_trace."""


def read(outcome):
    tr = outcome["trace"]
    kernel = [s for label, s in tr["device_ops"]
              if "paged_decode_attn" in label]
    if not kernel or not tr["busy_s"]:
        return None
    return 100.0 * sum(kernel) / tr["busy_s"]
