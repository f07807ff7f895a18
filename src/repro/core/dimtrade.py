"""TPC-DI v1.1.0 DimTrade transform: one Trade.txt CDC row becomes one
DimTrade row (``tpcdi_dimtrade_config``'s schema). Every lane is int32:
ids, ``YYYYMMDD`` date keys, epoch seconds and prices in cents all pass
2**24, where float32 stops being exact.

Per row:

* ``SK_AccountID``, ``SK_CustomerID``, ``SK_BrokerID`` from the DimAccount
  version in effect at ``T_DTS`` (the latest ``EffectiveDate <= T_DTS``;
  its ``EndDate`` is the next version's ``EffectiveDate``);
* ``SK_SecurityID``, ``SK_CompanyID`` from DimSecurity by symbol;
* ``Status`` from StatusType, ``Type`` from TradeType (constant tables);
* ``SK_CreateDateID`` / ``SK_CreateTimeID`` from ``T_DTS`` when the status
  is SBMT and the type TMB or TMS, or the status is PNDG; the close keys
  from ``T_DTS`` when the status is CMPT or CNCL; NULL (-1) otherwise, which
  an update leaves at the stored value (``KEEP_ON_UPDATE``);
* the rest copied; ``BatchID`` is the incremental batch's number.

A row is *found* when both dimensions answered and its domain codes are
valid; a row that is not waits in the late buffer. ``dimtrade_transform_kernel``
is the jitted body (one dispatch per block); ``dimtrade_np`` its host twin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NULL = -1
CDC_INSERT, CDC_UPDATE = 0, 1

TRADE_COLUMNS = ("cdc_flag", "cdc_dsn", "t_id", "t_dts", "t_st_id",
                 "t_tt_id", "t_is_cash", "t_s_symb", "t_qty", "t_bid_price",
                 "t_ca_id", "t_exec_name", "t_trade_price", "t_chrg",
                 "t_comm", "t_tax")
ACCOUNT_COLUMNS = ("sk_account_id", "account_id", "sk_broker_id",
                   "sk_customer_id", "status", "account_desc", "tax_status",
                   "effective_date")
SECURITY_COLUMNS = ("sk_security_id", "symbol", "issue", "status", "name",
                    "exchange_id", "sk_company_id", "shares_outstanding")
DIMTRADE_COLUMNS = ("trade_id", "sk_broker_id", "sk_create_date_id",
                    "sk_create_time_id", "sk_close_date_id",
                    "sk_close_time_id", "status", "type", "cash_flag",
                    "sk_security_id", "sk_company_id", "quantity",
                    "bid_price", "sk_customer_id", "sk_account_id",
                    "executed_by", "trade_price", "fee", "commission", "tax",
                    "batch_id")
BATCH_ID = 2        # the first incremental batch (batch 1: historical load)
KEEP_ON_UPDATE = tuple(DIMTRADE_COLUMNS.index(c) for c in (
    "sk_create_date_id", "sk_create_time_id", "sk_close_date_id",
    "sk_close_time_id"))

# StatusType.txt and TradeType.txt in file order: ST_ID / TT_ID codes are
# the row positions; the names are coded in alphabetical order
STATUS_TYPE = (("ACTV", "Active"), ("CMPT", "Completed"),
               ("CNCL", "Canceled"), ("PNDG", "Pending"),
               ("SBMT", "Submitted"), ("INAC", "Inactive"))
TRADE_TYPE = (("TMB", "Market Buy"), ("TMS", "Market Sell"),
              ("TSL", "Stop Loss"), ("TLS", "Limit Sell"),
              ("TLB", "Limit Buy"))


def _name_codes(table) -> np.ndarray:
    names = sorted(n for _, n in table)
    return np.array([names.index(n) for _, n in table], np.int32)


STATUS_NAME = _name_codes(STATUS_TYPE)
TYPE_NAME = _name_codes(TRADE_TYPE)
ST = {c: i for i, (c, _) in enumerate(STATUS_TYPE)}
TT = {c: i for i, (c, _) in enumerate(TRADE_TYPE)}

_T = {c: i for i, c in enumerate(TRADE_COLUMNS)}
_A = {c: i for i, c in enumerate(ACCOUNT_COLUMNS)}
_S = {c: i for i, c in enumerate(SECURITY_COLUMNS)}


def _civil(t, xp):
    """(YYYYMMDD, HHMMSS) of epoch seconds ``t`` (UTC, t >= 0), in integer
    arithmetic that stays inside int32 (days from civil, inverted)."""
    days, secs = t // 86400, t % 86400
    z = days + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = xp.where(mp < 10, mp + 3, mp - 9)
    y = yoe + era * 400 + (m <= 2)
    hh, rem = secs // 3600, secs % 3600
    return (y * 10000 + m * 100 + d,
            hh * 10000 + (rem // 60) * 100 + rem % 60)


def _rows(trade, acct, sec, found, xp):
    """The DimTrade rows of ``trade`` [n, 16] joined with each row's
    account version ``acct`` [n, 8] and security ``sec`` [n, 8]; the
    StatusType / TradeType lookups and the date rules. Shared by the
    jitted kernel (``xp`` = jnp) and the host twin (``xp`` = np)."""
    col = lambda c: trade[:, _T[c]]
    st, tt, dts = col("t_st_id"), col("t_tt_id"), col("t_dts")
    st_ok = (st >= 0) & (st < len(STATUS_TYPE))
    tt_ok = (tt >= 0) & (tt < len(TRADE_TYPE))
    status = xp.asarray(STATUS_NAME)[xp.clip(st, 0, len(STATUS_TYPE) - 1)]
    ttype = xp.asarray(TYPE_NAME)[xp.clip(tt, 0, len(TRADE_TYPE) - 1)]
    date_id, time_id = _civil(xp.maximum(dts, 0), xp)
    create = (((st == ST["SBMT"]) & ((tt == TT["TMB"]) | (tt == TT["TMS"])))
              | (st == ST["PNDG"]))
    close = (st == ST["CMPT"]) | (st == ST["CNCL"])
    null = xp.full_like(dts, NULL)
    out = xp.stack([
        col("t_id"), acct[:, _A["sk_broker_id"]],
        xp.where(create, date_id, null), xp.where(create, time_id, null),
        xp.where(close, date_id, null), xp.where(close, time_id, null),
        status, ttype, col("t_is_cash"),
        sec[:, _S["sk_security_id"]], sec[:, _S["sk_company_id"]],
        col("t_qty"), col("t_bid_price"),
        acct[:, _A["sk_customer_id"]], acct[:, _A["sk_account_id"]],
        col("t_exec_name"), col("t_trade_price"), col("t_chrg"),
        col("t_comm"), col("t_tax"), xp.full_like(dts, BATCH_ID)], axis=-1)
    return out, found & st_ok & tt_ok


@functools.partial(jax.jit, static_argnames=("asof_lane",))
def dimtrade_transform_kernel(trade, a_keys, a_vals, a_txn, s_keys, s_vals,
                              s_txn, asof_lane: int):
    """trade: [n, 16] int32 Trade CDC payloads. ONE dispatch: the
    point-in-time DimAccount probe, the DimSecurity probe, the domain
    lookups and the date keys. Returns (rows [n, 21] int32, found [n]
    bool)."""
    from repro.core.cache import lookup_asof, lookup_ref
    acct, a_found = lookup_asof(trade[:, _T["t_ca_id"]],
                                trade[:, _T["t_dts"]], a_keys, a_vals,
                                asof_lane)
    sec, s_found, _ = lookup_ref(trade[:, _T["t_s_symb"]], s_keys, s_vals,
                                 s_txn)
    return _rows(trade, acct, sec, a_found & s_found, jnp)


def dimtrade_np(trade, account_state, security_state, asof_lane: int):
    """Host twin of ``dimtrade_transform_kernel`` (the numpy backend)."""
    from repro.core.backend import _hash_probe_np, _lookup_asof_np
    trade = np.asarray(trade, np.int32)
    acct, a_found = _lookup_asof_np(trade[:, _T["t_ca_id"]],
                                    trade[:, _T["t_dts"]], *account_state[:2],
                                    asof_lane)
    sec, s_found, _ = _hash_probe_np(trade[:, _T["t_s_symb"]],
                                     *security_state)
    return _rows(trade, acct.astype(np.int32), sec.astype(np.int32),
                 a_found & s_found, np)
