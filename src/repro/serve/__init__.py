"""Serving subsystems.

``sc_engine`` / ``apps`` — the dynamic SC bank server (request admission,
bucketed padded BankPlans, per-request key threading).
"""
from ..core.executor import ExecOptions, ExecRequest
from ..core.faults import FaultModel
from .apps import app_netlist, app_request, circuit_request
from .sc_engine import (BankServer, BankServerStats, DeadlineExceeded,
                        RequestShed, SCRequest, ServerClosed, Ticket)

__all__ = [
    "BankServer", "BankServerStats", "DeadlineExceeded", "ExecOptions",
    "ExecRequest", "FaultModel", "RequestShed", "SCRequest", "ServerClosed",
    "Ticket",
    "app_netlist", "app_request", "circuit_request",
]
