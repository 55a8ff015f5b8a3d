"""Model FLOP/s utilization of training: the operations a step requires
per real token (bench/flops.py, over the traffic's cycle, which the
measured window runs whole) times train_tokens_per_s of that window, over
chips times the device's bf16 peak (bench/peaks.json)."""
import flops
from mt_traffic import MTTraffic


def read(ctx):
    t = MTTraffic(ctx["traffic"], ctx["spec"].vocab, 0)
    per_token = flops.train_flops_per_token(ctx["spec"], t.cycle_lengths,
                                            t.buckets[-1])
    peak = flops.peak(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * ctx["train_tokens_per_s"] / (
        ctx["chips"] * peak)
