"""Output tokens delivered to ``on_token`` inside the window, over the
window."""


def read(run):
    stamps = run.samples.get("token_s")
    if stamps is None:
        return None
    start, end = run.window
    return sum(1 for t in stamps if start <= t <= end) / (end - start)
