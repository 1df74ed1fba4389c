"""Model FLOP/s utilization, %: tokens per second times model FLOPs per
token (bench/flops.py, recomputation not counted) over the chips' bf16
peak (bench/peaks.json)."""


def read(run):
    if run.window_steps == 0 or run.window_s <= 0:
        return None
    rate = run.window_tokens / run.window_s
    return 100.0 * rate * run.flops_per_token / (
        run.chips * run.peak["bf16_flops_per_s"])
