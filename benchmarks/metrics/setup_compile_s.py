"""The program's ray_tpu_xla_compile_seconds (sum) at the window's opening:
compile or cache fetch.
"""

def read(obs):
    return obs["program_setup_compile_s"]
