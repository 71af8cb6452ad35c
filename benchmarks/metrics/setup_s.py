"""Process start (first line of run.py) to the window's opening: imports,
weights, compile or cache fetch, warm-up, the correctness check, the
traffic's lead-in.
"""

def read(obs):
    return obs["setup_s"]
