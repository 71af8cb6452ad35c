"""The program's ray_tpu_xla_compiles_total, close minus open: must be 0.
"""

def read(obs):
    return float(obs["program_window_compiles"])
