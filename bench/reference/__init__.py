"""The plain reference the output check compares the program against: the
simulated cluster (``lustre``), the action mapping (``spaces``), the DDPG
learner (``ddpg``) and whole tuning sessions (``session``). It imports
nothing of the program under ``src/``."""
