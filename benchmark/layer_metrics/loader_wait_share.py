"""Input pipeline: share of the window the dispatch loop spent inside
`next()` on the feed, from the harness's host spans. Fed cells only: a
resident feed has no loader to wait for, and the reader returns nothing."""


def read(ctx):
    if ctx["traffic"].get("feed") != "records" or not ctx["spans"]["next"]:
        return None
    return 100.0 * sum(ctx["spans"]["next"]) / ctx["window_s"]
