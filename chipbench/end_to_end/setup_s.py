"""Process start -> window opening: session, worker start and TPU open,
weights from the seed, warm-up of this cell's shapes, the correctness
probes, and the ramp that fills the slots or primes the feed."""


def read(obs):
    return obs["setup_s"]
