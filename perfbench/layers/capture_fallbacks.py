"""How often a capture fell back from device clones to host staging in
the window: the library's warnings, counted."""


def read(obs):
    if not obs.get("saves"):
        return None
    return obs.get("capture_fallbacks")
