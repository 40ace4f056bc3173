"""The benchmark: BENCHMARK.json's command, its yardsticks and its data files.

Nothing under this directory is imported by the program; the benchmark
imports the program (``megatronapp_tpu``) as the system under test.
"""
