"""Layer ``entry``: programs built (compiled or loaded from the persistent
cache) inside the measured window, counted by jax's own monitoring events.
Expected 0: every shape was warmed up during set-up."""


def read(obs):
    return float(obs["compiles_in_window"])
