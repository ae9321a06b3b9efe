"""Serving, closed loop, for a vision-language configuration whose grouped
attention runs UNDER A LEARNED INDEXER over a paged cache with heads, whose
rotary positions have three axes and are not the cache row, and nine in ten
of whose prompt rows a VISION TOWER makes of images (Keye-VL-2.0): the loop
of ``serve_closed_loop_ref.py`` AS IT IS (:func:`run` calls it, with this
file's set-up, stream and clients in the place of its own), so the same
``harness.Run`` and ``samples`` keys and every reader of a closed-loop cell
reads it.

**The stream** (:func:`client_stream`): sessions of images between text rows
asked several questions each, ``docqa_stream.py``'s blocks of lengths with
the images' grids drawn from the same ``order_seed``; tokens and pixels from
``--seed``. A request carries its images (:class:`SessionRequest`), which
:class:`Clients` hands ``engine.submit``.

**``correct``**, decided in two steps on what the timed path produces at the
timed sizes, as ``serve_closed_loop_dsa.py`` decides its own:

- before the window (:func:`reference_check`), on a FIXED session of the
  set-up: a document LONGER than ``index_topk`` (``CHECK_DOC`` = 6,144 rows
  of which seven images of mixed grids, both patch buckets among them) is
  registered in the trie BY THE ENGINE ITSELF (a request through ``submit``:
  its tower and chunk programs write the pages), and a second request finds
  it there: the engine's counters must show seven images skipped and none
  encoded. Then the document plus ``CHECK_OWN`` tokens is prefilled COLD
  (the trie off) and decoded ``CHECK_DECODE`` steps, and again ON THE HIT
  (no image encoded), by programs of the check's own (:class:`Served`: the
  engine's return tokens only) that take the ENGINE'S tower programs and
  stage. Compared with ``perfbench/reference/keyevl2_f32.py``: the tower's
  rows on their own first; the index scores at the ticks; the selected sets
  (``serve_closed_loop_dsa.selection_check``: a row chosen beside the
  reference's at a near-tie is held to the reference's k-th score, and
  counted); the logits over the SYSTEM'S sets and experts (tight) and over
  the reference's own sets (looser); the cached rows leaf by leaf (K, V,
  kI); every expert layer on the input it really saw
  (``serve_closed_loop_mla.layer_check``).
- after the window (:func:`engine_check`), the ENGINE'S OWN tick and chunk
  programs on a FIXED count of tokens of fixed lanes: what is in flight is
  cancelled, ``ENGINE_REQUESTS`` fixed questions of the fixed document are
  submitted together, COLD (the trie off: the engine's tower programs fill
  the stage and its chunk programs parse every image's position rows;
  whether the window left the document in the trie decides nothing), and
  stepped until each has ``ENGINE_TAIL`` tokens out. The rows their lanes
  hold at the last ``ENGINE_TAIL`` positions (ticks wrote them, each at its
  row plus the lane's installed ``rope_delta``) and at the last
  ``ENGINE_TAIL`` rows the tower made (a chunk program wrote them from the
  stage's slice, at the positions it parsed from its int operand), and the
  tokens returned, are held to ``Served``'s cold forward of the same
  sequences, which passes positions and the stage directly:
  ``ENGINE_REQUESTS x ENGINE_TAIL`` tokens in every run, never "however
  many were decoded when the window closed" (PERF.md section 7 records what
  that cost PR 58).

From ``serve_closed_loop_ref.py`` as it is: ``run`` (the loop),
``build_model`` (which makes an older program say at once, before any
compile, that it cannot run the configuration) and ``reference_module``;
from ``serve_closed_loop_mla.py``: ``build_engine``, ``layer_check``; from
``serve_closed_loop_dsa.py``: ``selection_check``; from
``serve_closed_loop_lfm2.py``: ``trie_off``; from
``serve_closed_loop_swa.py``: ``warm_up``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import types

import numpy as np

from perfbench import harness, serving, traffic as traffic_gen
from perfbench.drivers import docqa_stream
from perfbench.drivers import serve_closed_loop_dsa as dsa_driver
from perfbench.drivers import serve_closed_loop_lfm2 as lfm2_driver
from perfbench.drivers import serve_closed_loop_mla as mla_driver
from perfbench.drivers import serve_closed_loop_ref as ref_driver
from perfbench.drivers import serve_closed_loop_swa as swa_driver
from perfbench.drivers.serve_closed_loop_mla import _rms, build_engine

CHECK_DOC, CHECK_OWN, CHECK_DECODE = 6144, 192, 32
# the fixed document's images, rows x columns of ROWS (an image of 28 x as
# many pixels): both patch buckets, square and oblong
CHECK_GRIDS = ((32, 32), (32, 24), (24, 32), (28, 28), (16, 16), (20, 30),
               (30, 26))
TINY_GRIDS = ((2, 2), (4, 3), (3, 4), (4, 4), (2, 3), (3, 3), (2, 4))
ENGINE_REQUESTS, ENGINE_TAIL = 3, 64

# Limits from two readings each on the chip at the published widths (my chip
# runs, PR 61, ``chiprun_out/pr61``: the cell as built on seeds 6100000101-103
# and the probe on seeds 7 and 8, ``perfbench/probe_keyevl2.py``; PERF.md
# section 6): the largest reading of the engine as built over its seeds, and the
# smallest reading of what has to come out NOT correct. A fault is refused
# by one of the limits and not by each; every limit lies between its two
# readings. Logit errors are in units of the reference's logit deviation
# (0.905-0.913). The reference follows the system's experts and sets at
# EVERY position (``serve_closed_loop_dsa.py`` says why).
# - ``REFERENCE_RMS_TOL``, the logits against the reference attending over
#   the SYSTEM'S sets, hit and cold, all positions and the decode steps
#   alone: as built 0.00478-0.00493 on four seeds (bfloat16 weights and
#   activations through six layers; a third of DeepSeek-V3.2's, whose
#   latents round twice); the index key unrotated 0.0070, ReLU left out
#   0.0098, the position table left out 0.0102 at the decode steps, the head
#   weights left out 0.0208. The limit is 0.0059, the geometric middle of
#   0.00493 and 0.0070. A CHUNK'S SCORES ROUNDED TO BFLOAT16 read 0.00492
#   where as built read 0.00489: under per-head QK-norm a score stays near 1
#   and its rounding is lost among 2,048 rows; it is a reading of the probe
#   and no fault. THE PRECISION BELOW that these limits refuse is the
#   ROUTER'S (``bf16_router``: ``LAYER_WEIGHT_TOL``, below).
# - ``REFERENCE_MAX_TOL``, the largest error: as built 0.0265-0.027; the key
#   unrotated 0.041, the table left out 0.056, ReLU left out 0.059. The limit
#   is 0.05 (the largest error of 34 million logits swings between seeds: 1.85
#   times the largest reading; the rms limit has the rest).
# - ``OWN_SETS_RMS_TOL``, the same logits against the reference's OWN sets:
#   as built 0.00543-0.00564 (1.15 times the reading over the system's sets:
#   1% of a query's rows change places with rows that score alike); positions
#   of one axis 0.0093, the table left out 0.0118, ReLU left out 0.067. The
#   limit is 0.0072, the geometric middle of 0.00564 and 0.0093.
# - ``INDEX_TOL``, ``I`` at the decode steps, rms of the difference over the
#   rms of the reference's, the worst layer: as built 0.0135-0.0165 (0.0201
#   under bfloat16 scores, no fault); the table left out 0.065, positions of
#   one axis 0.106, the key unrotated 1.15, ReLU left out 1.83. The limit is
#   0.036, the geometric middle of 0.0201 and 0.065.
# - ``SET_SHARE_TOL``, the least share of a reference set the system also
#   chose: as built 0.9868-0.9888 on five seeds; positions of one axis 0.897,
#   ReLU left out 0.583 (the table left out 0.963: the tower's limit has
#   it). The limit is 0.94, the geometric middle of 0.9868 and 0.897.
# - ``SET_SCORE_TOL``, how far under the reference's k-th score a row chosen
#   beside the reference's set scores there, in units of the rms of that
#   query's scores: as built 0.043-0.060 (0.077 under bfloat16 scores); the
#   table left out 0.427, positions of one axis 0.667. The limit is 0.18,
#   the geometric middle of 0.077 and 0.427.
# - ``REFERENCE_ROWS_TOL``, the cached rows, K, V and kI apart, the worst
#   layer: as built 0.0048-0.0055 for each on five seeds; the table left out
#   0.0100-0.0106, THE INDEX KEY UNROTATED 1.06 for kI with K and V at 0.0067
#   (refused by this limit and the selection's). The limit is 0.0074, the
#   geometric middle of 0.0055 and 0.0100. (Positions of one axis read 0.0075
#   for K and 0.00745 for kI, a hair over it: the three limits of the
#   selection are what refuses them, by factors of 3 to 4.)
# - ``TOWER_ROWS_TOL``, the tower's rows in the engine's stage against the
#   reference's tower, the worst image: as built 0.00739-0.00766 on seven
#   images of both buckets; the position table left out 0.0513. The limit is
#   0.02, the geometric middle.
# - the expert layers on the input they really saw, with A.X-K1's limits
#   (``LAYER_WEIGHT_TOL`` 2e-6, ``LAYER_OUTPUT_TOL`` 0.012; the same
#   kernels): as built 2.4e-7 / 3.6e-7 and 0.0029; THE ROUTER'S PRODUCT IN
#   BFLOAT16 (the nearest precision below the float32 the configuration
#   states for it) 0.0147 on the weights, 31 of 1,344 layer-positions
#   choosing beside the reference, 0.0054 on the output (the probe's
#   ``bf16_router``, seed 8): the weight limit's alone, 7,000 times over it,
#   with every other reading as built.
# - the ENGINE'S OWN programs on 3 x 64 tokens of fixed lanes, prefilled
#   COLD, against ``Served``, which runs none of what the faults break (my
#   chip runs, PR 61, ``chiprun_out/pr61r``: ``probe_keyevl2.py --engine``
#   on seeds 7 and 8, and the cell as built on 23 runs in all):
#   ``ENGINE_ROWS_TOL``, the rows TICKS wrote (the last 64 of each lane, the
#   worst layer): as built 0.0080-0.0167 (0 in the first layer: ticks
#   against chunks differ by near-ties of the selection); a chunk's three
#   position rows holding the row's index 0.188-0.189 (0 in the first
#   layer), ``rope_delta`` not installed 0.86-0.87 in EVERY layer (the stage
#   a page off 0.058-0.061: the next limit's). The limit is 0.056, the
#   geometric middle of 0.0167 and 0.188 (A.X-K1's 0.19, borrowed at first,
#   passed the position rows' fault).
#   ``ENGINE_IMAGE_ROWS_TOL``, the rows a CHUNK program wrote from the
#   stage's slice (the last 64 the tower made): as built 0.0 to the bit in
#   every layer of every run (the engine's chunks are ``Served``'s); the
#   position rows' fault 0.82-0.83, the stage a page off 1.08-1.09, both in
#   every layer (``rope_delta`` leaves them alone: no tick writes them). The
#   limit is 0.1, an eighth of the smaller fault.
#   ``ENGINE_TOKEN_TOL``, the rms deficit of the engine's tokens under
#   ``Served``'s logits, in units of their deviation: as built 0-0.0077 on
#   22 runs (0-13 of 192 tokens beside ``Served``'s best, at near-ties); on
#   seed 8 the three faults 0.067-0.072 (19, 19 and 77 of 192 ``Served``'s
#   best); on seed 7 every fault 0.0 (with these weights the greedy token
#   was the last token's own: margin 0.36). The limit is 0.023, the
#   geometric middle of 0.0077 and 0.067; the rows refuse what it cannot.
REFERENCE_MAX_TOL = 0.05
REFERENCE_RMS_TOL = 0.0059
OWN_SETS_RMS_TOL = 0.0072
INDEX_TOL = 0.036
SET_SHARE_TOL = 0.94
SET_SCORE_TOL = 0.18
REFERENCE_ROWS_TOL = 0.0074
TOWER_ROWS_TOL = 0.02
LAYER_WEIGHT_TOL = mla_driver.LAYER_WEIGHT_TOL
LAYER_OUTPUT_TOL = mla_driver.LAYER_OUTPUT_TOL
ENGINE_ROWS_TOL = 0.056
ENGINE_IMAGE_ROWS_TOL = 0.1
ENGINE_TOKEN_TOL = 0.023


# ------------------------------------------------------------- the stream

@dataclasses.dataclass
class SessionRequest(traffic_gen.Request):
    """A request whose prompt marks image rows, with the images."""

    images: list = dataclasses.field(default_factory=list)


def _unit(group: dict) -> int:
    """Pixels of one ROW's side: a patch times the merge."""
    return int(group["patch_size"]) * int(group["merge"])


def session(order, tokens, traffic: dict, rows: int, group: dict,
            vocab: int):
    """``(ids, images)`` of one session of ``rows`` rows: before each image
    a caption of ``image.caption`` ids, then the image's ``h x w`` rows
    (``image_token_id``); grids from ``order``, ids and pixels from
    ``tokens``; images until the length is met, the last sized to fit what
    is left, text rows filling the rest."""
    spec, unit = traffic["image"], _unit(group)
    lo, hi = spec["side_min"] // unit, spec["side_max"] // unit
    step = max(spec["side_step"] // unit, 1)
    sides = np.arange(lo, hi + 1, step)
    caption, token = int(spec["caption"]), int(group["image_token_id"])
    parts, images, left = [], [], rows

    def text(n):
        parts.append(tokens.integers(1, vocab, n, dtype=np.int32))

    while left:
        h, w = (int(order.choice(sides)) for _ in range(2))
        if caption + h * w > left:      # the last one, sized to fit
            fits = [(a * b, a, b) for a in sides for b in sides
                    if caption + a * b <= left]
            if not fits:
                text(left)
                break
            _, h, w = max(fits)
        text(caption)
        parts.append(np.full(h * w, token, np.int32))
        images.append(tokens.integers(0, 256, (h * unit, w * unit, 3),
                                      dtype=np.uint8))
        left -= caption + h * w
    return np.concatenate(parts), images


def client_stream(traffic: dict, seed: int, client: int, vocab: int, *,
                  group: dict):
    """``docqa_stream.client_stream`` with sessions of images in the place
    of documents of tokens (module docstring). Text ids lie below
    ``image_token_id``."""
    block, questions = int(traffic.get("block", 4)), int(traffic["questions"])
    vocab = min(vocab, int(group["image_token_id"]))
    tokens = np.random.default_rng([seed, 1, client])
    order = np.random.default_rng([int(traffic["order_seed"]), 1, client])
    docs = docqa_stream._lengths(order, traffic["document"], block)
    asked = docqa_stream._lengths(order, traffic["question"], block)
    outputs = docqa_stream._lengths(order, traffic["output"], block)
    index = doc = 0
    while True:
        ids, images = session(
            order, tokens, traffic,
            docqa_stream.document_pages(traffic, next(docs)), group, vocab)
        first = client % questions if doc == 0 else 0
        for q in range(first, questions):
            question = tokens.integers(1, vocab, next(asked), dtype=np.int32)
            yield SessionRequest(index, 0.0, f"doc{doc}.q{q}",
                                 np.concatenate([ids, question]),
                                 int(next(outputs)), images)
            index += 1
        doc += 1


class Clients(serving.Clients):
    """``serving.Clients`` whose requests carry images."""

    def submit(self, request, due_s: float, **extra) -> dict:
        rec = {"request": request, "due_s": due_s, "stamps": [],
               "submit_s": time.perf_counter(), "id": None, **extra}
        try:
            rid = self.engine.submit(
                request.prompt, max_length=request.max_new_tokens,
                on_token=self._on_token, images=request.images or None)
        except self._refused:
            self.refused.append(rec)
            return rec
        rec["id"] = rid
        self.records[rid] = rec
        self.open.add(rid)
        return rec


# ------------------------------------------------------------ the set-up

def build_model(cell, seed: int):
    """``serve_closed_loop_ref.build_model``'s model and weights, with the
    tower's weights under ``vision`` and every norm's scale (the tower's
    among them) drawn ``1 + 0.1 N(0, 1)`` from the seed (the configuration's
    ``assumed``), in the configuration's ``weight_dtype``."""
    import flax
    import jax
    import jax.numpy as jnp

    # (an older program says here, before any compile, that its GPTConfig
    # lacks the configuration's fields, and ends)
    model, variables = ref_driver.build_model(cell, seed)

    from fleetx_tpu.models.vision.vit import tower_of

    tower = tower_of(model.cfg)
    group = model.cfg.vision_fields
    width = group["patch_size"] ** 2 * 3
    held = jnp.dtype(cell.config["weight_dtype"])

    @jax.jit
    def finish(scales, key):
        """The tower's weights and the norms' scales redrawn (the other
        leaves, 8.75 GB of them, never enter a program a second time)."""
        made = jax.tree.map(lambda x: x.astype(held), flax.core.meta.unbox(
            tower.init(key, jnp.zeros((4 * group["merge"] ** 2, width)),
                       jnp.asarray([2 * group["merge"]] * 2)))["params"])
        return made, [
            (leaf.astype(jnp.float32) + 0.1 * jax.random.normal(
                jax.random.fold_in(key, number), leaf.shape, jnp.float32)
             ).astype(held) for number, leaf in enumerate(scales)]

    def is_scale(path):
        return path[-1].key == "scale"

    key = jax.random.PRNGKey(seed + 17)
    made, _ = finish([], key)
    tree = {**variables["params"], "vision": made}
    leaves, shape = jax.tree_util.tree_flatten_with_path(tree)
    _, drawn = finish([leaf for path, leaf in leaves if is_scale(path)], key)
    drawn = iter(drawn)
    return model, {"params": jax.tree_util.tree_unflatten(shape, [
        next(drawn) if is_scale(path) else leaf for path, leaf in leaves])}


def check_sizes(cell) -> tuple:
    """``(document, own part, decode steps, grids, engine tail)``; a
    rehearsal's scale with its chunk."""
    if not cell.tiny:
        return CHECK_DOC, CHECK_OWN, CHECK_DECODE, CHECK_GRIDS, ENGINE_TAIL
    chunk = cell.deploy["prefill_chunk"]
    return 4 * chunk, chunk // 2, 4, TINY_GRIDS, 2


def fixed_session(cell, seed: int):
    """``(ids, images)`` of the check's fixed document and what follows it:
    ``doc`` rows (a caption and an image for every grid, then text), then
    ``own + decode`` ids."""
    group = cell.config["model"]["vision"]
    doc, own, decode, grids, _ = check_sizes(cell)
    caption = int(cell.traffic["image"]["caption"])
    vocab = min(cell.config["model"]["vocab_size"], group["image_token_id"])
    rng = np.random.default_rng([seed, 4])
    parts, images, unit = [], [], _unit(group)
    for h, w in grids:
        parts += [rng.integers(1, vocab, caption, dtype=np.int32),
                  np.full(h * w, group["image_token_id"], np.int32)]
        images.append(rng.integers(0, 256, (h * unit, w * unit, 3),
                                   dtype=np.uint8))
    used = sum(len(p) for p in parts)
    if used > doc:
        raise ValueError(f"the check's images take {used} of {doc} rows")
    parts.append(rng.integers(1, vocab, doc - used + own + decode,
                              dtype=np.int32))
    return np.concatenate(parts), images, rng


def warm_up(engine, cell, seed: int) -> list:
    """``serve_closed_loop_swa.warm_up`` (every chunk program a prompt can
    reach, and the tick), then one image of each patch bucket."""
    from fleetx_tpu.serving.rows_in import PATCH_BUCKETS

    group = cell.config["model"]["vision"]
    # (text ids lie below image_token_id: a stray one would mark a row)
    buckets = swa_driver.warm_up(engine, dataclasses.replace(
        cell, config={**cell.config, "model": {
            **cell.config["model"],
            "vocab_size": int(group["image_token_id"])}}), seed)
    unit, merge = _unit(group), int(group["merge"])
    rng = np.random.default_rng([seed, 6])
    sides = [max(int(np.sqrt(b)) // merge, 1) for b in PATCH_BUCKETS]
    if cell.tiny:
        sides = sides[:1]  # (a rehearsal's images lie in the first bucket)
        sides[0] = 2
    for side in sides:
        ids = np.concatenate([
            rng.integers(1, 100, 8, dtype=np.int32),
            np.full(side * side, group["image_token_id"], np.int32),
            rng.integers(1, 100, 8, dtype=np.int32)])
        engine.submit(ids, max_length=2, images=[rng.integers(
            0, 256, (side * unit, side * unit, 3), dtype=np.uint8)])
        engine.drain()
    return buckets


# ------------------------------------------------------------- the check

def lane_rows(engine, lane: int, lo: int, hi: int) -> np.ndarray:
    """The rows the engine's pool holds for ``lane`` at positions ``[lo,
    hi)`` of every layer, read through the manager's HOST table: ``[layers,
    hi - lo, K + V + kI]`` float32 (without the third leaf's padding)."""
    cfg, manager = engine.model.cfg, engine.cache_manager
    pos = np.arange(lo, hi)
    page = (manager.lane_tables(lane)[pos // manager.page_size][None, :]
            + np.arange(cfg.num_layers)[:, None] * manager.num_pages)
    pools = lfm2_driver._pools(engine)
    rows = [np.asarray(pools[name][page, pos % manager.page_size], np.float32)
            for name in ("cached_key", "cached_value", "cached_index")]
    return np.concatenate(rows[:2] + [rows[2][..., :cfg.index_head_dim]], -1)


class Served:
    """What the model computes through the ENGINE'S pool, by programs of the
    check's own (the engine's return tokens only, so logits, routing and
    selection need them): a chunk of ``engine.prefill_chunk`` rows that
    takes its rows' positions (three axes) and the tower's rows from the
    ENGINE'S stage, written there by the ENGINE'S tower programs; and a step
    SHAPED AS THE ENGINE'S TICK (one row of every lane in order, the check's
    lane alone decoding, its position its row plus ``rope_delta``). In a
    lane of ``engine.cache_manager`` claimed by the rows' KEYS, so that the
    trie matches, shares and registers pages exactly as for a request. Every
    chunk keeps the choices of ALL its rows (``serve_closed_loop_dsa.
    Served`` says why). ``model`` is the engine's unless a probe plants a
    fault."""

    def __init__(self, engine, model=None, params=None):
        import jax
        import jax.numpy as jnp

        self.engine, self.params = engine, params
        model = model or engine.model
        token = engine._tower.image_token_id
        donate = (1,) if jax.default_backend() == "tpu" else ()

        def named(routing, pick):
            return {jax.tree_util.keystr(path[-2:-1]).strip("[']"): pick(leaf)
                    for path, leaf in jax.tree_util.tree_flatten_with_path(
                        routing)[0]}

        @functools.partial(jax.jit, donate_argnums=donate,
                           static_argnames=("tail", "logits"))
        def chunk(params, cache, ids, pos, at, count, table, stage, tail=0,
                  logits=True):
            n = ids.shape[0]
            rows = jnp.arange(n, dtype=jnp.int32)
            wanted = logits  # (False: the head's product is dead code)
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                ids[None], pos[:, None, :], None, decode=True,
                cache_positions=at[None], block_tables=table[None],
                input_rows=(jax.lax.dynamic_slice_in_dim(stage, at, n)[None],
                            ((ids == token) & (rows < count))[None]),
                mutable=["cache"] + (["routing"] if tail else []))
            if not tail:
                return mut["cache"], None, None

            def last(x):  # [rows, ...] -> its last ``tail`` tokens
                return jax.lax.dynamic_slice_in_dim(x, count - tail, tail, 0)

            return (mut["cache"],
                    last(logits[0]).astype(jnp.float32) if wanted else None,
                    named(mut["routing"],
                          lambda leaf: jax.vmap(last)(leaf[:, 0])))

        @functools.partial(jax.jit, donate_argnums=donate)
        def tick(params, cache, token_id, at, position, lane, tables):
            active = jnp.arange(tables.shape[0]) == lane
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                jnp.where(active, token_id, 0)[:, None],
                jnp.broadcast_to(jnp.where(active, position, 0)[None, :, None],
                                 (3, tables.shape[0], 1)), None,
                decode=True, block_tables=tables,
                cache_positions=jnp.where(active, at, engine.cache_len - 1),
                mutable=["cache", "routing"])
            return (mut["cache"], logits[lane].astype(jnp.float32),
                    named(mut["routing"],
                          lambda leaf: jax.lax.dynamic_index_in_dim(
                              leaf, lane, 1, False)))

        @jax.jit
        def rate(logits, tokens):
            top = jax.lax.top_k(logits, 2)[0]
            at = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
            return top[:, 0] - at, top[:, 0] - top[:, 1]

        self._chunk, self._tick, self._rate = chunk, tick, rate

    def _params(self):
        return self.engine.params if self.params is None else self.params

    def _call(self, lane: int, req, ids, at: int, tail: int = 0,
              logits: bool = True):
        import jax.numpy as jnp

        from fleetx_tpu.serving.rows_in import row_positions

        engine, manager = self.engine, self.engine.cache_manager
        rows = engine.prefill_chunk
        engine._tower.stage_rows(req, at, len(ids))
        padded = np.zeros(rows, np.int32)
        padded[:len(ids)] = ids
        pos = np.zeros((3, rows), np.int32)
        pos[:, :len(ids)] = row_positions(req, at, len(ids))
        manager.cache, logits, sown = self._chunk(
            self._params(), manager.cache, jnp.asarray(padded),
            jnp.asarray(pos), jnp.asarray(at, jnp.int32),
            jnp.asarray(len(ids), jnp.int32),
            jnp.asarray(manager.lane_tables(lane)), engine._tower.stage,
            tail=tail, logits=logits)
        return logits, sown

    def prefill(self, lane: int, req, tokens, start: int, tail: int,
                keep: bool = False):
        """``tokens[start:]`` written at rows ``start`` on, in chunks of
        ``engine.prefill_chunk`` (a first chunk with the remainder, then
        whole ones). Returns the logits of the last ``tail`` rows and the
        routing there; ``keep``: every chunk gives the choices of ALL its
        rows (``self.choices``)."""
        chunk, n = self.engine.prefill_chunk, len(tokens)
        first = (n - start) % chunk or min(chunk, n - start)
        starts = [start] + list(range(start + first, n, chunk))
        if min(first if len(starts) == 1 else chunk, n - start) < tail:
            raise ValueError(f"{n - start} rows from {start} on give no "
                             f"tail of {tail}")
        self.choices = {"experts": [], "index_sets": []}
        for at in starts:
            ids = tokens[at:at + (first if at == start else chunk)]
            wanted = len(ids) if keep else tail if at == starts[-1] else 0
            logits, sown = self._call(lane, req, ids, at, wanted,
                                      logits=at == starts[-1])
            if keep:
                self._keep(sown)
        if keep:
            return logits[-tail:], {k: v[:, -tail:] for k, v in sown.items()}
        return logits, sown

    def _keep(self, sown) -> None:
        self.choices["experts"].append(np.asarray(sown["experts"], np.int32))
        self.choices["index_sets"].append(
            np.asarray(sown["index_sets"][..., :self.columns]))

    def step(self, lane: int, req, token: int):
        import jax.numpy as jnp

        manager = self.engine.cache_manager
        if not manager.ensure_page(lane):
            raise RuntimeError("the pool ran dry in the check")
        tables = np.zeros_like(manager.tables)
        tables[lane] = manager.tables[lane]
        at = int(manager.lengths[lane])
        manager.cache, logits, sown = self._tick(
            self._params(), manager.cache, jnp.asarray(token, jnp.int32),
            jnp.asarray(at, jnp.int32),
            jnp.asarray(at + req.rope_delta, jnp.int32),
            jnp.asarray(lane, jnp.int32), jnp.asarray(tables))
        manager.lengths[lane] += 1
        return np.asarray(logits), sown

    def deficits(self, logits, tokens) -> tuple:
        import jax.numpy as jnp

        deficit, margin = self._rate(logits, jnp.asarray(tokens, jnp.int32))
        return np.asarray(deficit), np.asarray(margin)

    def request_of(self, tokens, images, prompt_len: int):
        """What ``engine.submit`` derives of a prompt with images, as a
        request the tower's programs and ``row_positions`` take."""
        from fleetx_tpu.serving.rows_in import layout

        keys, positions, delta, records = layout(
            np.asarray(tokens[:prompt_len], np.int32), images,
            self.engine.model.cfg.vision_fields)
        return types.SimpleNamespace(
            id=-1, prompt_len=prompt_len, keys=keys, positions=positions,
            rope_delta=delta, images=records, staged=set())

    def sequence(self, tokens, images, prompt_len: int, tail: int) -> dict:
        """The first ``prompt_len`` rows of ``tokens`` admitted (the trie
        matching what it holds of their KEYS) and prefilled from the match's
        end, the rest decoded a tick each: ``matched``; ``logits`` (the last
        ``tail`` prompt rows, then every decode step); the routing and the
        selection there; every position's choices (``experts_all``,
        ``sets_all``); the lane's ``rows`` at the positions compared;
        ``staged``, the images the tower encoded for it."""
        manager = self.engine.cache_manager
        self.columns = len(tokens)
        req = self.request_of(tokens, images, prompt_len)
        lane, matched = manager.alloc(-1, req.keys)
        try:
            logits, sown = self.prefill(lane, req, tokens[:prompt_len],
                                        matched, tail, keep=True)
            out = [np.asarray(logits)]
            routing = {k: [np.asarray(v, np.float32)] for k, v in sown.items()}
            for token in tokens[prompt_len:]:
                logits, sown = self.step(lane, req, int(token))
                out.append(logits)
                self._keep(sown)
                for k, v in sown.items():
                    routing.setdefault(k, []).append(
                        np.asarray(v, np.float32))
            compared = tail + len(tokens) - prompt_len
            rows = lane_rows(self.engine, lane, len(tokens) - compared,
                             len(tokens))
        finally:
            manager.free(lane)
        return {"matched": int(matched), "logits": np.concatenate(out),
                "rows": rows, "staged": len(req.staged),
                "experts_all": np.concatenate(self.choices["experts"], 1),
                "sets_all": np.concatenate(self.choices["index_sets"], 1),
                **{k: np.concatenate(v, axis=1) for k, v in routing.items()}}


def tower_check(engine, variables, cell, tokens, images) -> dict:
    """The tower's rows ON THEIR OWN: what the engine's tower programs left
    in the stage at the images' rows (the cold run has just staged every
    one) against the reference's tower, image by image."""
    group = cell.config["model"]["vision"]
    reference = ref_driver.reference_module(cell).configured_tower(
        cell.config["model"])
    marked = np.flatnonzero(np.asarray(tokens) == group["image_token_id"])
    staged = np.asarray(engine._tower.stage[marked], np.float32)
    errs, at = [], 0
    for image in images:
        theirs = np.asarray(reference(variables["params"], image))
        mine = staged[at:at + len(theirs)]
        errs.append(float(np.sqrt(((mine - theirs) ** 2).mean()
                                  / (theirs ** 2).mean())))
        at += len(theirs)
    return {"tower_images_checked": len(errs),
            "tower_rows_checked": int(at),
            "tower_rows_rel_rms_err": max(errs),
            "tower_rows_rel_rms_err_by_image": errs,
            "tower_rows_tol": TOWER_ROWS_TOL}


def reference_check(engine, variables, cell, seed: int,
                    served: Served = None) -> dict:
    """The engine against the configuration's float32 reference, outside
    the window: module docstring."""
    served = served or Served(engine)
    model = cell.config["model"]
    logits = ref_driver.reference_module(cell).configured(model)
    doc, own, decode, grids, _ = check_sizes(cell)
    tokens, images, rng = fixed_session(cell, seed)
    n, tail, top = len(tokens), own + decode, model["index_topk"]
    vocab = min(model["vocab_size"], model["vision"]["image_token_id"])

    # another question registers the document, through the engine itself;
    # a second one finds it there: no image of it is encoded again
    def ask():
        engine.submit(np.concatenate([tokens[:doc], rng.integers(
            1, vocab, engine.page_size * 2, dtype=np.int32)]), max_length=2,
            images=images)
        engine.drain()
        snap = engine.metrics.snapshot()
        return snap["images_encoded"], snap["images_skipped"]

    peaks = {}  # the device's peak so far, after each step of the check

    def done(step):
        peaks[step] = harness.memory_peak_bytes(cell.chips) / 1e9

    first, second = ask(), ask()
    done("engine_asked")
    out = {"engine_hit_images_encoded": second[0] - first[0],
           "engine_hit_images_skipped": second[1] - first[1],
           "reference_peak_gb_after": peaks}

    def reference(mine, own_sets: bool):
        got = logits(
            variables["params"], tokens, images=images, tail=tail,
            with_all=True, given=mine["experts_all"],
            given_sets=None if own_sets else mine["sets_all"])
        return {k: np.asarray(v) for k, v in got.items()
                if v is not None}

    def rows_err(mine, theirs):
        cfg = engine.model.cfg
        w = cfg.kv_heads * cfg.head_dim
        return [float(lfm2_driver._rel_rms(
            theirs["rows"][..., part], mine["rows"][..., part], (1, 2)).max())
            for part in (slice(None, w), slice(w, 2 * w), slice(2 * w, None))]

    with lfm2_driver.trie_off(engine.cache_manager.pool):
        cold = served.sequence(tokens, images, doc + own, own)
    done("served_cold")
    out.update(tower_check(engine, variables, cell, tokens, images))
    done("tower")
    under = reference(cold, own_sets=False)     # over the system's sets
    done("reference")
    unit = float(under["logits"].std())
    hit = served.sequence(tokens, images, doc + own, own)
    engine.cache_manager.pool.check_invariants()
    cold_err = np.abs(cold["logits"] - under["logits"])
    out.update(dsa_driver.selection_check(cold, under, n, top))
    out["selection_tol"] = [INDEX_TOL, SET_SHARE_TOL, SET_SCORE_TOL]
    leaves = rows_err(cold, under)
    same = all(np.array_equal(cold[k], hit[k])
               for k in ("experts", "index_sets"))
    under_hit = under
    if not same:  # the document's choices are the cold run's either way
        at = hit["matched"]
        under_hit = reference({k: np.concatenate([cold[k][:, :at], hit[k]], 1)
                               for k in ("experts_all", "sets_all")},
                              own_sets=False)
    err = np.abs(hit["logits"] - under_hit["logits"])
    leaves = np.maximum(leaves, rows_err(hit, under_hit)).tolist()
    own_err = np.abs(cold["logits"]
                     - reference(cold, own_sets=True)["logits"])
    out.update({
        "reference_logit_std": unit,
        "reference_positions_checked": int(err.shape[0]),
        "hit_matched_tokens": hit["matched"],
        "cold_matched_tokens": cold["matched"],
        "hit_images_encoded": hit["staged"],
        "cold_images_encoded": cold["staged"],
        "hit_cold_same_choices": bool(same),
        "reference_max_abs_err": float(err.max()),
        "reference_rms_err": _rms(err),
        "reference_decode_rms_err": _rms(err[own:]),
        "reference_cold_max_abs_err": float(cold_err.max()),
        "reference_cold_rms_err": _rms(cold_err),
        "reference_cold_decode_rms_err": _rms(cold_err[own:]),
        "reference_own_sets_rms_err": _rms(own_err),
        "reference_own_sets_max_abs_err": float(own_err.max()),
        "hit_cold_logit_rms_diff": _rms(hit["logits"] - cold["logits"]),
        "reference_k_rel_rms_err": leaves[0],
        "reference_v_rel_rms_err": leaves[1],
        "reference_ki_rel_rms_err": leaves[2],
        "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL,
                                 OWN_SETS_RMS_TOL],
        "reference_rows_tol": REFERENCE_ROWS_TOL})
    positions = hit["experts"].shape[1]
    layers = mla_driver.layer_check(hit, variables, cell,
                                    under["experts"][:, -positions:])
    out.update(layers)
    out["selection_ok"] = bool(
        out["sets_sizes_right"] and out["index_rel_rms_err"] <= INDEX_TOL
        and out["sets_shared_min"] >= SET_SHARE_TOL
        and out["sets_beside_max_under_kth"] <= SET_SCORE_TOL)
    out["images_ok"] = bool(
        out["tower_rows_rel_rms_err"] <= TOWER_ROWS_TOL
        and out["engine_hit_images_encoded"] == 0
        and out["engine_hit_images_skipped"] == len(grids)
        and hit["staged"] == 0 and cold["staged"] == len(grids))
    out["reference_ok"] = bool(
        layers["layers_ok"] and out["selection_ok"] and out["images_ok"]
        and hit["matched"] == doc and cold["matched"] == 0
        and max(leaves) <= REFERENCE_ROWS_TOL
        and max(out["reference_max_abs_err"],
                out["reference_cold_max_abs_err"]) <= REFERENCE_MAX_TOL * unit
        and max(out["reference_rms_err"], out["reference_decode_rms_err"],
                out["reference_cold_rms_err"],
                out["reference_cold_decode_rms_err"])
        <= REFERENCE_RMS_TOL * unit
        and out["reference_own_sets_rms_err"] <= OWN_SETS_RMS_TOL * unit)
    return out


def engine_check(engine, served: Served, unit: float, cell,
                 seed: int) -> dict:
    """The ENGINE'S OWN PROGRAMS against the checked ones (``Served``, which
    ``reference_check`` holds to the reference) on a FIXED count of tokens
    of fixed lanes (module docstring)."""
    t0 = time.perf_counter()
    manager = engine.cache_manager
    doc, _, _, _, tail = check_sizes(cell)
    for req in (list(engine._active.values())
                + list(engine._prefilling.values())
                + list(engine.scheduler.snapshot())):
        engine.cancel(req.id)
    tokens, images, rng = fixed_session(cell, seed)
    vocab = min(cell.config["model"]["vocab_size"],
                cell.config["model"]["vision"]["image_token_id"])
    asked = [np.concatenate([tokens[:doc], rng.integers(
        1, vocab, engine.page_size * (3 + i), dtype=np.int32)])
        for i in range(ENGINE_REQUESTS)]
    encoded = engine.metrics.snapshot()["images_encoded"]
    # COLD, whatever the window left in the trie: the engine's tower
    # programs fill the stage and its chunk programs take every image's rows
    # and positions from it (on a hit they would see the questions alone)
    with lfm2_driver.trie_off(manager.pool):
        # (a lane admitted first decodes on through the others' prefills,
        # a chunk or a tower program a step: none may finish before the last
        # has its tail)
        steps = sum(-(-len(p) // engine.prefill_chunk) + len(images)
                    for p in asked)
        ids = [engine.submit(p, max_length=tail + 16 + steps, images=images)
               for p in asked]
        for _ in range(100000):
            live = {r.id: r for r in engine._active.values()}
            if all(i in live and len(live[i].tokens) > tail for i in ids):
                break
            engine.step()
        else:
            raise RuntimeError("the engine check's requests never all stood "
                               f"{tail} tokens past their prompts")
        engine._settle("other")
    encoded = engine.metrics.snapshot()["images_encoded"] - encoded
    # the last ``tail`` rows of the document that the TOWER made (the end of
    # its last image): a chunk program wrote them from the stage, at the
    # positions it parsed
    token = cell.config["model"]["vision"]["image_token_id"]
    made = int(np.flatnonzero(tokens[:doc] == token)[-1]) + 1
    held = []
    for lane, req in sorted(engine._active.items()):
        if req.id not in ids:
            continue
        seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
        n = int(manager.lengths[lane])        # rows [0, n) hold seq[:n]
        if n != len(seq) - 1:
            raise RuntimeError(f"lane {lane} holds {n} rows for "
                               f"{len(seq)} tokens")
        held.append((seq, len(req.prompt),
                     lane_rows(engine, lane, n - tail, n),
                     lane_rows(engine, lane, made - tail, made)))
    out = {"engine_lanes_checked": len(held),
           "engine_images_encoded": int(encoded)}
    for i in ids:
        engine.cancel(i)
    rows_err, image_err, deficits, margins = [], [], [], []
    for seq, prompt_len, rows, image_rows in held:
        n = len(seq) - 1
        req = served.request_of(seq, images, prompt_len)
        with lfm2_driver.trie_off(manager.pool):
            lane, _ = manager.alloc(-1, np.concatenate(
                [req.keys, seq[prompt_len:n].astype(np.int64)]))
        try:
            logits, _ = served.prefill(lane, req, seq[:n], 0, tail)
            rows_err.append(lfm2_driver._rel_rms(
                rows, lane_rows(engine, lane, n - tail, n), (1, 2)))
            image_err.append(lfm2_driver._rel_rms(
                image_rows, lane_rows(engine, lane, made - tail, made),
                (1, 2)))
        finally:
            manager.free(lane)
        # row i predicts token i + 1; the engine chose those from the
        # prompt's last row on
        chosen = np.arange(n - tail, n) >= prompt_len - 1
        deficit, margin = served.deficits(logits, seq[n - tail + 1:])
        # exactly ``tail`` of them: the last ``tail`` the engine chose
        deficits.append(deficit[chosen][-tail:])
        margins.append(margin[chosen][-tail:])
    manager.pool.check_invariants()
    rows_err = np.asarray(rows_err).reshape(len(held), -1)   # [lanes, layers]
    image_err = np.asarray(image_err).reshape(len(held), -1)
    deficits, margins = np.concatenate(deficits), np.concatenate(margins)
    out.update({
        "engine_rows_checked": int(tail * len(held)),
        "engine_rows_max_rel_rms_err": float(rows_err.max()),
        "engine_rows_rel_rms_err_by_layer": [
            float(e) for e in rows_err.max(0)],
        "engine_image_rows_max_rel_rms_err": float(image_err.max()),
        "engine_image_rows_rel_rms_err_by_layer": [
            float(e) for e in image_err.max(0)],
        "engine_tokens_served_checked": int(deficits.size),
        "engine_tokens_served_best": int((deficits == 0).sum()),
        "engine_token_served_max_deficit": float(deficits.max()),
        "engine_token_served_rms_deficit": _rms(deficits),
        "served_margin_p50": float(np.median(margins)),
        "engine_tol": [ENGINE_ROWS_TOL, ENGINE_IMAGE_ROWS_TOL,
                       ENGINE_TOKEN_TOL],
        "engine_check_s": time.perf_counter() - t0})
    out["engine_ok"] = bool(
        len(held) == ENGINE_REQUESTS
        and encoded == ENGINE_REQUESTS * len(images)
        and deficits.size == ENGINE_REQUESTS * tail
        and out["engine_rows_max_rel_rms_err"] <= ENGINE_ROWS_TOL
        and out["engine_image_rows_max_rel_rms_err"] <= ENGINE_IMAGE_ROWS_TOL
        and out["engine_token_served_rms_deficit"] <= ENGINE_TOKEN_TOL * unit)
    return out


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    """``serve_closed_loop_ref.run`` with this file's set-up, stream and
    clients in the place of its own, then the engine check, and ``correct``
    decided anew from the same parts."""
    held = {}

    def set_up(cell, seed, t_process):
        device = harness.own_the_chip(cell.chips, cell.tiny)

        from fleetx_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        clock = harness.CompileClock()
        phases = {"import_s": time.perf_counter() - t_process}
        peak = {}  # the device's peak so far, after each phase of the set-up

        def done(phase):
            phases[phase + "_s"] = time.perf_counter() - t_process
            peak[phase] = harness.memory_peak_bytes(cell.chips) / 1e9

        model, variables = build_model(cell, seed)
        engine = build_engine(cell, model, variables)
        done("weights_and_engine")
        buckets = warm_up(engine, cell, seed)
        done("warm_up")
        served = Served(engine)
        reference = reference_check(engine, variables, cell, seed, served)
        done("reference")
        held.update(engine=engine, served=served, reference=reference,
                    peak=peak)
        return device, clock, engine, reference, buckets, phases

    loop_cell = dataclasses.replace(cell, traffic={
        **cell.traffic, "clients": cell.traffic["closed_loop"]["clients"]})
    theirs = ref_driver.set_up, ref_driver.traffic_gen, ref_driver.serving
    ref_driver.set_up = set_up
    ref_driver.traffic_gen = types.SimpleNamespace(
        client_stream=functools.partial(
            client_stream, group=cell.config["model"]["vision"]))
    ref_driver.serving = types.SimpleNamespace(
        Clients=Clients, serving_checks=serving.serving_checks,
        counters=serving.counters)
    try:
        out = ref_driver.run(loop_cell, seed, seconds, trace, t_process)
    finally:
        (ref_driver.set_up, ref_driver.traffic_gen,
         ref_driver.serving) = theirs
    out.cell = cell
    harness.log("tower, index and routing counters " + str({
        k: v for k, v in out.counters.items()
        if k.startswith(("image", "tower_", "index_", "rows_selected", "moe_",
                         "prefill_tokens_", "prefix_"))}))
    if out.trace and out.traced:  # what the kernels' rooflines are read from
        ticks = [s.attrs.get("selected_rows", 0) for s in out.spans_named(
            "serving.decode") if out.traced[0] <= s.start_s <= out.traced[1]]
        harness.log("traced stretch " + str({
            "family_s": out.trace["family_s"],
            "family_calls": out.trace["family_calls"],
            "ticks": len(ticks), "selected_rows_mean": float(
                np.mean(ticks)) if ticks else None,
            "window_s": out.trace["window_s"]}))
    engine, checks = held["engine"], out.checks
    checks["memory_peak_gb_after"] = dict(
        held["peak"], window=harness.memory_peak_bytes(cell.chips) / 1e9)
    checks.update(engine_check(
        engine, held["served"], held["reference"]["reference_logit_std"],
        cell, seed))
    checks["correct"] = bool(
        not checks["wrong_results"] and not checks["refused"]
        and not checks["engine_recoveries"] and not checks["poison_retired"]
        and not any(checks["fault_events"].values())
        and (checks["mosaic_calls"] > 0 or cell.tiny)
        and checks["compiles_in_window"] == 0
        and checks["reference_ok"] and checks["engine_ok"])
    out.correct = checks["correct"]
    return out
