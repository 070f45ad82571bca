"""idle_share.train: per cent of the traced window in which no operation ran on
the device (1 - union of device-op intervals / window).  Moves
train_img_per_s."""
from lib.readers import idle_share


def read(ctx, red):
    return idle_share(red)
