"""The chip benchmark's own code: registry, reference arithmetic, traffic,
trace reduction and the cell runner. Imports nothing of ``repro`` at
module level; only ``cell`` reaches the program, and only inside a run.
"""
