"""Mean server-side reply time of the window's requests: from the answer's
resolution to its RESULT frame handed to the socket (packing, the
device-to-host copy in encoding, the write), from the program's
``serve.reply_ms`` histogram."""

import program_metrics as pm


def read(ctx):
    return pm.hist_mean_in_window(ctx, "serve.reply_ms")
