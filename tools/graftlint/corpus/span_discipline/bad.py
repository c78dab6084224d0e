"""span-discipline bad corpus: span-less execute-path functions and a
client method that calls the transport directly instead of the span-
injecting _do layer."""

import urllib.request

from obs import tracing  # corpus stand-in


def _batch_pair_counts(ops, stacks):
    # BAD: batch executor stage with no tracing span — invisible stretch
    # in every query profile
    out = []
    for op in ops:
        out.append(len(stacks))
    return out


def _group_by(levels):
    # BAD: a span under a name the span table does not have
    with tracing.start_span("executor.groupByNotInTheTable"):
        return len(levels)


class Executor:
    def execute(self, index, query, shards):
        # BAD: the top-level execute entry point opens no span
        results = []
        for call in query.calls:
            results.append(self._execute_call(index, call, shards))
        return results

    def _execute_call(self, index, call, shards):
        return call


class InternalClient:
    def _do_full(self, method, uri, path, body=None):
        headers = {}
        span = tracing.active_span()
        if span is not None:
            tracing.get_tracer().inject_headers(span.context, headers)
        return self._pool.request(method, uri + path, body, headers, timeout=5)

    def query_node(self, uri, index, query, shards):
        # BAD: public method hits the pool directly — skips trace-header
        # injection and the deadline budget
        status, data, ctype = self._pool.request(
            "POST", uri + f"/index/{index}/query", query, {}, timeout=5
        )
        return data

    def status(self, uri):
        # BAD: raw urlopen from a client that owns a _do layer
        return urllib.request.urlopen(uri + "/status", timeout=5).read()
