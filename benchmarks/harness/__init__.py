"""The benchmark's yardstick: traffic, weights, reference, reducers.

Nothing here imports the program (``lir_tpu``) except ``builders`` and the
two window drivers, which hold the system under test.
"""
