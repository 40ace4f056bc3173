"""Model: tokens/s/chip x the benchmark's own operations per token (the
configuration's model module: perfbench/flops.py for a dense GPT) / the
chip's published bf16 peak (perfbench/peaks.json), in percent. Recomputed
operations do not count."""


def read(run):
    if run.get("kind") != "train" or not run.get("peaks"):
        return None
    flops = run["model"].flops_per_token(run["config"], run["seq_length"])
    return 100.0 * run["tok_s_chip"] * flops / run["peaks"]["bf16_flops_per_s"]
