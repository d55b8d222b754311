"""Device ms per step of the step's ops that name no stage, in themselves
or in the computation they call: the reshapes of the optimizer and
error-feedback state between the step's stages."""
from metrics import stage_ms


def read(red):
    return stage_ms(red, "unstaged")
