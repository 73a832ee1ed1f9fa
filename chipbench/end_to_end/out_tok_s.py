"""Output tokens received inside the window / window, whether or not
their request ended in it."""


def read(obs):
    if not obs["client"]:
        return None
    return obs["client"]["tokens_in_window"] / obs["seconds"]
