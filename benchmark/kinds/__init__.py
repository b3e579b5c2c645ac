"""One module per traffic kind. A traffic file names its kind under
``"kind"``; the module has ``run(ctx) -> result``, and the serving kinds a
pure ``schedule(params, seconds)``."""
