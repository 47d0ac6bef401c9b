"""XLA compiles (``jax.monitoring`` backend-compile events, which JAX
also fires when it loads a program from its persistent cache) after the
window opened; 0 when every shape was warmed in set-up."""


def read(run):
    return run.compiles_in_window
