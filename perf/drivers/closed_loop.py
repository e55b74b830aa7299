"""Closed loop: a fixed number of clients, each sending its next request
when its last one resolves, so a slow system receives less load. The
measure is work completed per second.
"""
from __future__ import annotations

import queue
import time

import traffic as traffic_lib
from drivers import serve_common
from drivers.serve_common import log


class ClosedServe(serve_common.Serve):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.free_clients = queue.Queue()

    def finished(self, req) -> None:
        self.free_clients.put(req.client)


def run(ctx: dict) -> dict:
    serve = ClosedServe(ctx)
    serve.build()
    pool = traffic_lib.closed_loop_pool(serve.mix, serve.vocab, serve.seed)
    serve.compile_warmup()
    clients = int(serve.mix['clients'])
    warm_s = float(serve.mix['warmup_s'])
    sent = []
    cursor = 0

    def send_next(client: int) -> None:
        nonlocal cursor
        src = pool[cursor % len(pool)]
        cursor += 1
        r = traffic_lib.Request(len(sent), src.prompt, src.max_new)
        r.client = client
        sent.append(r)
        serve.send(r)

    t_start = time.monotonic()
    for c in range(clients):
        send_next(c)
    t_open = t_start + warm_s
    t_close = t_open + serve.seconds
    opened = False
    while True:
        now = time.monotonic()
        if not opened and now >= t_open:
            opened = True
            serve.open_window(t_open)
            log(f'window opens; {clients} clients')
        if now >= t_close:
            break
        try:
            c = serve.free_clients.get(timeout=0.02)
        except queue.Empty:
            continue
        send_next(c)
    serve.close_window()
    # The window is shut: what is still under way is neither counted nor
    # awaited. Its clients are told to stop.
    for r in sent:
        if r.done_time is None and r.future is not None:
            r.future.cancel()
    in_window = [r for r in sent
                 if r.done_time is not None
                 and t_open <= r.done_time < t_close]
    failed = 0
    for r in in_window:
        if not serve.served_tokens_ok(r):
            failed += 1
            if r.future is not None and r.future.done() and \
                    not r.future.cancelled():
                r.error = r.error or repr(r.future.exception())
            log(f'request {r.index} failed: {r.error} '
                f'({len(r.tokens)}/{r.max_new} tokens)')
    time.sleep(0.3)   # let the engine see the cancellations
    return serve_common.finish(serve, in_window, sent, t_open,
                               attempted=len(in_window), failed=failed)
