"""decode_mfu.backlog: the decode step's share of the chip's bf16 peak
in a backlog mix: useful (uncoded) FLOPs, from shapes, of every token
that a decode round delivered to the host inside the window, over the
window's seconds (host clock). A token at output index i (i >= 1;
index 0 is the prefill's) attends to prompt_len + i positions."""


def read(run):
    if run.kind != "backlog" or run.peaks is None:
        return None
    end = run.seconds * 1e3
    flops = 0.0
    for r in run.records:
        p = r.planned.prompt_len
        for i, t in enumerate(r.token_ms):
            if i and 0.0 <= t < end:
                flops += run.model.token_flops(run.hf, p + i)
    return 100.0 * flops / run.seconds / run.peaks["bf16_flops"]
