"""One module per model family: how a configuration file becomes the
program's config, the family's plain float32 reference, and the functions
that count the operations and bytes its shapes need. A configuration file
names its module under ``"model"``."""
