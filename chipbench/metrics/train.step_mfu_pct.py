"""Share of the chip's bf16 peak that the training steps reach while
their programs run: the BDWP operation count per token (the family's
``train_flops_per_token``) times the tokens trained in the window, over
the device time of the programs that ran in the traced window (the
window runs only the train step) times the peak.  Time between programs is the train loop's, read
by ``train.device_idle_pct``."""

from chipbench import trace as TRC


def read(ctx):
    res = ctx["res"]
    progs = TRC.program_times(ctx["trace"], ctx["window"])
    ctx["log"](f"programs in the window (s summed over chips, runs): {progs}")
    secs = sum(s for s, _ in progs.values())
    if not res.get("tokens") or secs <= 0:
        return None
    work = res["flops_per_token"]["sparse"] * res["tokens"]
    return 100.0 * work / (secs * ctx["peaks"]["bf16_flops_per_s"])
