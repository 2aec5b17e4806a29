"""Bytes on disk under the newest step over the state's bytes."""


def read(obs):
    if not obs.get("stored_bytes"):
        return None
    return obs["stored_bytes"] / obs["state_bytes"]
