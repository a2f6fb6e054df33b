"""step_mfu (%): the model FLOPs of the window's steps over the window's
wall time, over the chip's bf16 peak.

Model FLOPs follow PaLM (arXiv 2204.02311, App. B): 6 FLOPs per matmul
parameter per position that passes through it (the layers' matrices for
every position, the output head for the text positions; not the
embedding lookup), plus 6 * L * H * Q * T per position for causal
attention over T positions. Recomputation (the backward scan re-runs each
layer's forward) and the optimizer's projections are not counted.
"""


def matmul_params(model: dict) -> dict:
    D, F, L = model["d_model"], model["d_ff"], model["num_layers"]
    H, KV = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or D // H
    layer = D * H * hd * 2 + D * KV * hd * 2 + 3 * D * F
    return {"layers": L * layer, "head": D * model["vocab_size"]}


def flops_per_step(model: dict, traffic: dict) -> float:
    B, T = traffic["batch"], traffic["seq"]
    text = T - traffic.get("prefix", 0)
    p = matmul_params(model)
    H = model["num_heads"]
    hd = model.get("head_dim") or model["d_model"] // H
    attn = 6 * model["num_layers"] * H * hd * T * T
    return B * (6 * p["layers"] * T + 6 * p["head"] * text + attn)


def read(ctx):
    w = ctx.window
    if not w["steps"] or not ctx.peaks:
        return None
    flops = flops_per_step(ctx.cell.config["model"], ctx.cell.traffic)
    return 100.0 * flops * w["steps"] / w["seconds"] \
        / ctx.peaks["bf16_flops_per_s"]
