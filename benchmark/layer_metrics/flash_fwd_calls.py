"""Step programs: how many distinct flash-attention forward instructions
(`flash_fwd.N`, named by `ops/pallas_attention.py`) ran in the window: the
forward passes one step program holds. `benchmark/flops.py` counts the four
a GAN step needs (D on the real and on the fake batch, G once, D again in
the G half); the program as compiled holds five, because G's forward runs
in the D half and again in the G half and XLA does not merge two custom
calls. A PR that shares G's forward between the halves reads 4 here and
`flash_fwd_ms` falls by a fifth. The names are counted over the whole
traced window, which the harness fills with the step program alone:
`reduced["ops"]` does not say which program an operation belongs to, so
a window that also ran a sampler would count its kernels too."""


def read(ctx):
    r = ctx["reduced"]
    if r is None:
        return None
    names = {name for name, _ in r["ops"]
             if name.startswith("pallas:") and "flash_fwd" in name}
    return float(len(names)) if names else None
