"""1 - (union of device-operation intervals / traced window), in %, averaged
over the chips used."""


def read(context):
    device = context["device"]
    return 100.0 * (1.0 - device["busy_s"] / device["window_s"])
