"""Layer: step programs. Model FLOP/s utilisation: the operations the
forward and backward passes REQUIRE per sample (no recomputation;
``harness/opcount.py``, the function the configuration names) times the
samples per second of this run's window, over chips times the published
peak. Above 100% is a fault of the count and raises."""

from perfbench.harness import device, opcount


def read(outcome):
    c, cfg, mix = outcome["counters"], outcome["config"], outcome["mix"]
    t0, t1 = c["window"]
    rate = c["steps"] * c["samples_per_step"] / (t1 - t0)
    fn = getattr(opcount, cfg["train_flops"])
    per_sample = (fn(cfg, mix["seq_len"]) if "seq_len" in mix else fn(cfg))
    dev = outcome["device"]
    peak = device.peaks(dev["kind"])["flops_per_s"] * dev["count"]
    return opcount.share_percent(per_sample * rate / peak, 1.0, "train_mfu")
