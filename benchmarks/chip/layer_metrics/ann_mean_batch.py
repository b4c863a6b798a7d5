"""Queries per endpoint batch: requests over batches, ``stats()`` deltas."""


def read(sample):
    endpoint = sample.get("endpoint")
    if not endpoint or not endpoint["batches"]:
        return None
    return endpoint["requests"] / endpoint["batches"]
