"""Peak device memory in use over the memory the runtime offers, in %
(``memory_stats()`` after the window): a fuller HBM leaves the KV pool
less room, which caps the fleet."""


def read(run):
    if not run.memory.get("limit"):
        return None
    return 100.0 * run.memory["peak"] / run.memory["limit"]
