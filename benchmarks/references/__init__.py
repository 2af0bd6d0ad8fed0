"""Plain references, one module per model family; a configuration's file
names its module under ``lir_tpu.reference``. Nothing here imports the
program."""
