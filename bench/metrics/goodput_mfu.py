"""Model FLOPs of the steps completed in the window over the window's
seconds times the chips' peak (bench/flops.py, bench/peaks.json), in %."""


def read(rec):
    peak = rec.get("peaks", {}).get("bf16_flops_per_s")
    if "tokens" not in rec or not peak or rec["window_s"] <= 0:
        return None
    flops = rec["tokens"] * rec["flops_per_token"]
    return 100.0 * flops / (rec["window_s"] * peak * rec["chips"])
