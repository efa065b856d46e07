"""window_held_share.tok (%, lower is better; layer: cluster and
scheduler; moves serve_tok_s). What the window layers hold of what they
would hold had they kept every token: the sum over the traced window's
``serving.dispatch`` spans of ``window_pages`` (pages all window layers
hold live keys in) over the number of window layers times
``shared_kv_pages`` (the pages the same sequences hold in a pool that
keeps the whole context). About window / context. A program without the
counters reports nothing."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    kinds = getattr(run.family, "kinds", None)
    if sp is None or kinds is None:
        return None
    got = [s[3] for s in spans.named(sp["host"], spans.DISPATCH)
           if "window_pages" in s[3] and s[3].get("shared_kv_pages")]
    whole = kinds(run.cfg).get("window", 0) \
        * sum(g["shared_kv_pages"] for g in got)
    return 100.0 * sum(g["window_pages"] for g in got) / whole \
        if whole else None
