"""The whole step's share of the chip's peak: operations the forward and
backward passes require per token (the configuration's module under
benchmarks/costs/, remat's recomputation not counted) x tokens per second of the window, over the
peak of the device kind (layer: model step)."""

import importlib

from benchmarks.harness import peaks


def read(ctx):
    rate = ctx["facts"].get("tokens_per_s")
    if not rate or "costs" not in ctx["config"]:
        return None
    costs = importlib.import_module("benchmarks.costs." + ctx["config"]["costs"])
    flops = costs.train_flops_per_token(ctx["config"], ctx["facts"]["seq_len"])
    return 100.0 * flops * rate / peaks.chip_peaks(ctx["device_kind"])["flops"]
