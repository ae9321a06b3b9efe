"""Scheduler and cache: of the bytes of state a tick reads in BOTH homes of
a lane, the share that is latents: the latent pages in use after the window
(``kv_page_bytes_in_use``: the rows the one latent layer's decode attends
over, whole pages) over those plus the matrix state every lane holds once a
lane (``state_bytes_lanes``: read, and written, once a tick). It says which
home sets the tick's pace as answers grow: a lane's matrix state is constant,
its latents grow by a row a token. None for a family that lacks either
home."""


def read(run):
    latents = run.counters.get("latent_pages_in_use")
    page = run.counters.get("latent_page_bytes")
    lanes = run.counters.get("state_bytes_lanes")
    if not latents or not page or not lanes:
        return None
    return latents * page / (latents * page + lanes)
