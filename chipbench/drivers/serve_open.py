"""``kind: serve_open`` — independent users: arrivals on a schedule from
the seed at the rate the mix fixes, whatever the server does."""

from chipbench import loadgen, serving


def start_traffic(client, cell, seed, vocab, start, end, probes,
                  rate=None):
    rate = rate or cell.traffic["arrivals"]["rate_per_s"]
    schedule = loadgen.open_schedule(cell.traffic, seed, vocab, rate,
                                     start, end)
    return client.run(client.open_loop(schedule, probes)), lambda: None


def run(cell, args) -> dict:
    return serving.run(cell, args, start_traffic)
