"""Fleet launcher: N replica processes behind one router + API process.

The deployable shape of the serving stack (docs/SERVING.md
"Deployment"):

    python tools/serve.py --demo --replicas 2 --port 8000

spawns one REPLICA subprocess per ``--replicas`` — each is started with
an environment in which it sees exactly ONE chip (:func:`_replica_env`;
a chip belongs to one process at a time, so ``--replicas N`` needs an
N-chip host), builds its own engine on it, serves it over the
:mod:`~fleetx_tpu.serving.api.replica_server` RPC on an ephemeral
port, and hands that port back through a port file — then runs the
FRONT DOOR in this process, which is pinned to the host platform so it
can never take a chip (the router derives request RNG keys with jax): a
:class:`~fleetx_tpu.serving.router.ServingRouter` over
:class:`~fleetx_tpu.serving.api.replica_client.ReplicaClient` proxies,
fronted by the OpenAI-compatible
:class:`~fleetx_tpu.serving.api.server.ApiServer`. Any stock OpenAI
client or curl can then stream chat completions; a replica process
dying mid-stream is absorbed by the router's zero-token-loss
migration.

SIGTERM (or Ctrl-C) runs the graceful drain fan-out: router admission
stops, every replica gets ``request_shutdown`` over RPC (in-flight
requests finish, ``finish_reason="shutdown"`` at the grace deadline),
replica processes get SIGTERM and are reaped, and the launcher exits 0.

``--demo`` serves the deterministic tiny GPT the test-suite uses
(token-id text codec: prompts like ``"12 7 3"``) — the model surface
real deployments replace by loading a checkpoint; the launcher,
router, RPC and API layers are the same either way.

Env knobs (docs/ENV_VARS.md): ``FLEETX_SERVE_REPLICAS``,
``FLEETX_API_PORT``, ``FLEETX_API_HOST``, ``FLEETX_SERVE_GRACE_S``.

Internal: ``--replica-worker`` is the subprocess entry point (not for
operators) — it builds the engine, serves RPC, writes its port file,
and drains on SIGTERM.
"""

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _replica_env(index: int) -> dict:
    """The environment of replica child ``index``: the parent's, plus the
    libtpu chip-visibility variables that make the child's TPU backend
    see exactly chip ``index`` as a one-chip topology of its own (set
    here, before the child imports jax; a CPU run ignores them). The two
    bounds are needed as well as the chip index: with the index alone the
    second child dies on libtpu's multi-process lockfile (four-chip v5e
    host, libtpu 0.0.34)."""
    return {
        **os.environ,
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def _popen_replica(index: int, port_file: str, grace_s: float):
    """Start replica child ``index`` (the worker entry of this file) on
    its own chip."""
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--replica-worker", "--replica-index", str(index),
         "--port-file", port_file, "--grace-s", str(grace_s)],
        env=_replica_env(index),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_demo_engine(seed: int = 0):
    """The deterministic tiny-GPT engine (the suite's serving fixture) on
    this process's default device — a replica child sees only its own
    chip (:func:`_replica_env`)."""
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.serving import ServingEngine

    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1,
        num_attention_heads=2, ffn_hidden_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, dtype=jnp.float32,
        use_flash_attention=False)
    gen_cfg = GenerationConfig(decode_strategy="greedy",
                               eos_token_id=10**6, pad_token_id=60,
                               max_length=8)
    model = GPTForPretraining(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((2, 8), jnp.int32))
    return ServingEngine(model, params, slots=4, cache_len=32,
                         gen_cfg=gen_cfg, prefill_bucket=4, page_size=8)


def run_replica_worker(args) -> int:
    """Subprocess entry: engine + RPC server + port-file handshake,
    drain-and-exit-0 on SIGTERM."""
    import jax

    from fleetx_tpu.serving.api.replica_server import ReplicaServer
    from fleetx_tpu.utils.compile_cache import enable_compile_cache
    from fleetx_tpu.utils.log import logger

    enable_compile_cache()
    engine = _build_demo_engine()
    server = ReplicaServer(engine, port=args.rpc_port).start()
    tmp = args.port_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(str(server.port))
    os.replace(tmp, args.port_file)  # atomic: parent never reads partial
    dev = jax.devices()[0]
    logger.info("serve: replica %d ready on %s (%s %s, %d device(s) "
                "visible)", args.replica_index, server.url, dev.platform,
                dev.device_kind, len(jax.devices()))

    stopping = []

    def on_term(signum, frame):
        stopping.append(signum)

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    while not stopping:
        time.sleep(0.05)
    # graceful: stop admitting, finish what's in flight, then exit 0.
    # (the router usually drove request_shutdown over RPC already —
    # request_shutdown is idempotent.)
    engine.request_shutdown(args.grace_s)
    engine.drain(max_ticks=2000)
    server.stop()
    return 0


def _spawn_replicas(n: int, grace_s: float, tmpdir: str):
    """Launch the replica subprocesses; returns (procs, urls) once every
    port file has appeared (raises after 120 s — a replica that can't
    bind or import is a launch failure, not a hang)."""
    procs, port_files = [], []
    for i in range(n):
        pf = os.path.join(tmpdir, f"replica_{i}.port")
        port_files.append(pf)
        procs.append(_popen_replica(i, pf, grace_s))
    deadline = time.monotonic() + 120
    urls = []
    for i, pf in enumerate(port_files):
        while not os.path.exists(pf):
            if procs[i].poll() is not None:
                raise RuntimeError(
                    f"replica {i} exited rc={procs[i].returncode} "
                    "before publishing its port")
            if time.monotonic() > deadline:
                raise RuntimeError(f"replica {i} never published its port")
            time.sleep(0.05)
        with open(pf) as f:
            urls.append(f"http://127.0.0.1:{int(f.read().strip())}")
    return procs, urls


def spawn_replica(tmpdir: str, index: int, grace_s: float = 30.0,
                  connect_wait_s: float = 30.0):
    """Launch ONE additional replica subprocess and hand back a
    connected ``ReplicaClient`` plus its process — the autoscaler's
    ``spawn_fn`` seam (``--autoscale``; docs/SERVING.md "Per-tenant QoS
    & autoscaling"). Same worker entry + port-file handshake as the
    launch-time fleet, so a scale-up replica is indistinguishable from
    a launch-time one. Raises on launch failure (the caller decides
    whether that aborts or just skips this scale-up)."""
    from fleetx_tpu.serving.api.replica_client import ReplicaClient

    pf = os.path.join(tmpdir, f"replica_{index}.port")
    if os.path.exists(pf):
        os.remove(pf)  # a reused index must not read a stale port
    proc = _popen_replica(index, pf, grace_s)
    deadline = time.monotonic() + 120
    while not os.path.exists(pf):
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica {index} exited rc={proc.returncode} "
                "before publishing its port")
        if time.monotonic() > deadline:
            proc.kill()
            raise RuntimeError(f"replica {index} never published its port")
        time.sleep(0.05)
    with open(pf) as f:
        url = f"http://127.0.0.1:{int(f.read().strip())}"
    return ReplicaClient(url, connect_wait_s=connect_wait_s), proc


def run_fleet(args) -> int:
    """Parent entry: replicas → router-over-RPC → API, then serve until
    SIGTERM and drain the whole fleet."""
    from fleetx_tpu.serving.api.replica_client import ReplicaClient
    from fleetx_tpu.serving.api.server import ApiServer
    from fleetx_tpu.serving.router import ServingRouter
    from fleetx_tpu.utils.log import logger

    replicas = args.replicas or int(
        os.environ.get("FLEETX_SERVE_REPLICAS", "2"))
    grace_s = (args.grace_s if args.grace_s is not None
               else float(os.environ.get("FLEETX_SERVE_GRACE_S", "30")))
    port = (args.port if args.port is not None
            else int(os.environ.get("FLEETX_API_PORT", "8000")))
    host = args.host or os.environ.get("FLEETX_API_HOST", "127.0.0.1")

    # the chips are the replica children's: this process only derives
    # request RNG keys (ServingRouter), which the host platform does. A
    # jax config pin, not an environment variable — the children inherit
    # the environment and must still find their chip.
    import jax

    jax.config.update("jax_platforms", "cpu")
    with tempfile.TemporaryDirectory(prefix="fleetx_serve_") as tmpdir:
        procs, urls = _spawn_replicas(replicas, grace_s, tmpdir)
        api = None
        try:
            clients = [ReplicaClient(u, connect_wait_s=30) for u in urls]
            router = ServingRouter(clients)
            scaler = None
            if args.autoscale:
                from fleetx_tpu.serving.autoscaler import FleetAutoscaler

                next_index = [replicas]

                def spawn():
                    try:
                        client, proc = spawn_replica(
                            tmpdir, next_index[0], grace_s)
                    except Exception as e:  # noqa: BLE001 — skip this round
                        logger.error("serve: scale-up spawn failed: %s", e)
                        return None
                    procs.append(proc)
                    next_index[0] += 1
                    return client

                scaler = FleetAutoscaler(router, spawn,
                                         min_replicas=replicas,
                                         grace_s=grace_s)
            api = ApiServer(router, port=port, host=host,
                            model_id=args.model_id).start()
            if args.api_port_file:
                tmp = args.api_port_file + ".tmp"
                with open(tmp, "w") as f:
                    f.write(str(api.port))
                os.replace(tmp, args.api_port_file)
            logger.info(
                "serve: fleet of %d replicas up — OpenAI API at %s/v1 "
                "(model id %r)", replicas, api.url, args.model_id)

            stopping = []

            def on_term(signum, frame):
                stopping.append(signum)

            signal.signal(signal.SIGTERM, on_term)
            signal.signal(signal.SIGINT, on_term)
            while not stopping:
                if all(p.poll() is not None for p in procs):
                    logger.error("serve: every replica process exited; "
                                 "shutting the front door down")
                    break
                if scaler is not None:
                    scaler.step()
                time.sleep(0.1)

            logger.info("serve: draining fleet (grace %.0fs)", grace_s)
            router.shutdown(grace_s)  # fan-out request_shutdown over RPC
        finally:
            if api is not None:
                api.stop()
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=grace_s + 30)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=10)
    logger.info("serve: fleet drained; bye")
    return 0


def main(argv=None) -> int:
    """CLI entry point."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--demo", action="store_true",
                    help="serve the deterministic tiny demo GPT")
    ap.add_argument("--replicas", type=int, default=None,
                    help="replica process count "
                         "(default $FLEETX_SERVE_REPLICAS or 2)")
    ap.add_argument("--port", type=int, default=None,
                    help="API port (default $FLEETX_API_PORT or 8000; "
                         "0 = ephemeral)")
    ap.add_argument("--host", default=None,
                    help="API bind host (default $FLEETX_API_HOST or "
                         "127.0.0.1)")
    ap.add_argument("--model-id", default="fleetx-demo",
                    help="model id served at /v1/models")
    ap.add_argument("--grace-s", type=float, default=None,
                    help="drain grace (default $FLEETX_SERVE_GRACE_S or 30)")
    ap.add_argument("--autoscale", action="store_true",
                    help="close the fleet-sizing loop: a FleetAutoscaler "
                         "watches replica health and spawns/drains replica "
                         "processes (FLEETX_AUTOSCALE_* knobs)")
    ap.add_argument("--api-port-file", default=None,
                    help="write the bound API port here once serving "
                         "(handshake for tests/scripts)")
    # internal subprocess plumbing
    ap.add_argument("--replica-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--replica-index", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--rpc-port", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--port-file", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.replica_worker:
        return run_replica_worker(args)
    if not args.demo:
        ap.error("only --demo is wired up so far: real checkpoints plug "
                 "in by replacing _build_demo_engine")
    return run_fleet(args)


if __name__ == "__main__":
    sys.exit(main())
