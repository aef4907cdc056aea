"""Trainer loop (stood in for by the harness loop): mean host time inside
`pt.step` per step, from the harness's host spans. Enqueue time, not
device time."""


def read(ctx):
    spans = ctx["spans"]["step"]
    return 1e3 * sum(spans) / len(spans) if spans else None
