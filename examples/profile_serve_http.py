"""Per-hop profile of the Serve HTTP request path.

Builds the rate ladder the 1-core qps gap analysis needs (PERF.md "Serve
HTTP path"), every step measured in THIS process within one window:

  1. raw aiohttp echo        — the Python HTTP stack ceiling, no ray
  2. router-only control     — call_async, no HTTP
  3. in-process proxy        — real Router + aiohttp handler on the MAIN
                               thread, cProfile enabled on that thread so
                               the profile shows where request handling
                               actually spends its time (handler, router
                               bridge, result delivery, response encode)
  4. full Serve HTTP         — out-of-process proxy actor (call_async)

Run:  JAX_PLATFORMS=cpu python examples/profile_serve_http.py
"""

import cProfile
import io
import json
import os
import pstats
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # runnable as `python examples/...`

CONCURRENCY = 16
WINDOW = 0.7
REPS = 3

NOOP_CONFIG = {"num_replicas": 2, "max_batch_size": 32,
               "batch_wait_timeout": 0.001, "max_concurrent_queries": 8}


def median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def http_load(pool, port, seconds=WINDOW, path="/noop"):
    """Timed keep-alive GET window at CONCURRENCY; returns qps."""
    import http.client
    import threading

    tls = threading.local()
    stop = time.perf_counter() + seconds

    def worker(_):
        n = 0
        conns = getattr(tls, "conns", None)
        if conns is None:
            conns = tls.conns = {}
        while time.perf_counter() < stop:
            conn = conns.get(port)
            if conn is None:
                conn = conns[port] = http.client.HTTPConnection(
                    "127.0.0.1", port)
            conn.request("GET", path)
            conn.getresponse().read()
            n += 1
        return n

    t0 = time.perf_counter()
    counts = list(pool.map(worker, range(CONCURRENCY)))
    return sum(counts) / (time.perf_counter() - t0)


# -- step 1: raw aiohttp ----------------------------------------------------

def raw_aiohttp_qps(pool):
    import asyncio
    import threading

    from aiohttp import web

    ready = threading.Event()
    port_box = {}
    loop_box = {}

    def serve():
        async def handler(request):
            return web.json_response({"result": "ok"})

        async def run():
            loop_box["loop"] = asyncio.get_running_loop()
            app = web.Application()
            app.router.add_route("*", "/{tail:.*}", handler)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port_box["port"] = site._server.sockets[0].getsockname()[1]
            ready.set()
            while True:
                await asyncio.sleep(3600)

        try:
            asyncio.run(run())
        except RuntimeError:
            pass

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    ready.wait(10)
    http_load(pool, port_box["port"], 0.2)
    rates = [http_load(pool, port_box["port"]) for _ in range(REPS)]
    loop_box["loop"].call_soon_threadsafe(loop_box["loop"].stop)
    return median(rates)


# -- step 2: router-only ----------------------------------------------------

def router_only_qps(router):
    import asyncio

    def window():
        async def drive():
            stop = time.perf_counter() + WINDOW

            async def worker():
                n = 0
                while time.perf_counter() < stop:
                    await router.call_async(None)
                    n += 1
                return n

            t0 = time.perf_counter()
            counts = await asyncio.gather(
                *[worker() for _ in range(CONCURRENCY)])
            return sum(counts) / (time.perf_counter() - t0)

        return asyncio.run(drive())

    window()
    return median([window() for _ in range(REPS)])


# -- step 3: in-process proxy under cProfile --------------------------------

def inprocess_proxy_profile(pool, controller):
    """Real Router + the same aiohttp handler shape as HTTPProxy, but the
    event loop runs on THIS thread so cProfile sees the whole server-side
    request path (client threads stay unprofiled in the pool)."""
    import asyncio

    from aiohttp import web

    from ray_tpu.serve.router import Router

    router = Router(controller, "noop")
    out = {}

    async def main():
        async def handler(request):
            result = await router.call_async(None, timeout=60.0)
            return web.json_response({"result": result})

        app = web.Application()
        app.router.add_route("*", "/{tail:.*}", handler)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, http_load, pool, port, 0.2)
        prof = cProfile.Profile()
        prof.enable()
        rates = []
        for _ in range(REPS):
            rates.append(
                await loop.run_in_executor(None, http_load, pool, port))
        prof.disable()
        out["qps"] = median(rates)
        out["prof"] = prof
        await runner.cleanup()

    asyncio.run(main())
    router.close()
    return out


def summarize_profile(prof) -> tuple[str, dict]:
    """Top functions + tottime grouped by layer (file path)."""
    buf = io.StringIO()
    st = pstats.Stats(prof, stream=buf)
    st.sort_stats("cumulative").print_stats(25)
    layers = {"aiohttp": 0.0, "serve/router": 0.0, "serve/http_proxy": 0.0,
              "core_worker": 0.0, "rpc": 0.0, "memstore": 0.0,
              "serialization": 0.0, "asyncio/selector": 0.0, "other": 0.0}
    for (fn, _line, _name), (cc, nc, tt, ct, callers) in st.stats.items():
        for key in layers:
            if key in fn.replace("\\", "/"):
                layers[key] += tt
                break
        else:
            if "asyncio" in fn or "selectors" in fn:
                layers["asyncio/selector"] += tt
            else:
                layers["other"] += tt
    return buf.getvalue(), {k: round(v, 3) for k, v in layers.items()}


def main():
    import ray_tpu
    from ray_tpu import serve

    pool = ThreadPoolExecutor(max_workers=CONCURRENCY)
    ladder = {}

    ladder["raw_aiohttp_qps"] = round(raw_aiohttp_qps(pool), 1)

    ray_tpu.init(num_cpus=4)
    client = serve.start(http=True)
    client.create_backend("noop", lambda _=None: "ok", config=NOOP_CONFIG)
    client.create_endpoint("noop", backend="noop", route="/noop")
    handle = client.get_handle("noop")
    ray_tpu.get(handle.remote(None))

    ladder["router_only_qps"] = round(
        router_only_qps(handle._router), 1)

    res = inprocess_proxy_profile(pool, client._controller)
    ladder["inprocess_proxy_qps"] = round(res["qps"], 1)
    report, layers = summarize_profile(res["prof"])
    ladder["inprocess_proxy_tottime_by_layer_s"] = layers

    # full path: the proxy actor from serve.start
    http_load(pool, client.http_port, 0.2)
    ladder["serve_http_qps"] = round(median(
        [http_load(pool, client.http_port) for _ in range(REPS)]), 1)

    print(report)
    print(json.dumps(ladder, indent=1))
    pool.shutdown()
    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
