"""Training step: model FLOP/s utilisation — tokens per second times the
forward+backward FLOPs a token REQUIRES (harness/flops.py: 6 per matmul
parameter, input embedding excluded, plus causal attention; recomputed
operations not counted), over chips times the published bf16 peak. An
end-to-end utilisation, not a kernel's roofline share."""
from harness import flops


def read(rec):
    if rec["kind"] != "train" or rec.get("peaks") is None \
            or not rec["n_steps"]:
        return None
    t0, t1 = rec["window"]
    tokens_per_s = rec["n_steps"] * rec["tokens_per_step"] / (t1 - t0)
    need = flops.train_flops_per_token(rec["model"], rec["seq"])
    return tokens_per_s * need / (rec["chips"] * rec["peaks"]["bf16_flops"])
